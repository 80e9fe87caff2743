"""The latent decode kernel's least time at the chip's peaks over its
device time, in percent, for the decode launches the trace holds (the
reference's ``mla_decode`` work)."""

from rtbench import held


def read(run):
    return held.roofline(run, "decode", "mla_decode_kernel",
                         run.work.get("mla_decode"))
