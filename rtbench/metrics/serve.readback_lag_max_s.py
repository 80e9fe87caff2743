"""Longest seconds, in the traced window, from a decode launch's end on
the device to the end of the ``readback:decode`` span that waited for
it (``rtbench/serve_spans.py``): a stall in the readback reads here."""

from rtbench import serve_spans


def read(run):
    spans = serve_spans.of(run)
    if not spans or not spans["decode_lags_s"]:
        return None
    return max(spans["decode_lags_s"])
