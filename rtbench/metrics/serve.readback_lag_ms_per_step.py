"""Mean milliseconds from a decode launch's end on the device to the end
of the completion worker's ``readback:decode`` span that waited for it,
per decode step (``rtbench/serve_spans.py``)."""

from rtbench import serve_spans


def read(run):
    spans = serve_spans.of(run)
    if not spans or not spans["decode_lags_s"]:
        return None
    lags = spans["decode_lags_s"]
    steps_per_launch = (run.res["decode_steps_executed"]
                        / run.res["decode_dispatches"])
    return 1000.0 * sum(lags) / len(lags) / steps_per_launch
