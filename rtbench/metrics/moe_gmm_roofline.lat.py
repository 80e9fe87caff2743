"""The grouped expert matmul's least time at the chip's peaks over its
device time in the decode launches, in percent: the serve's counted
pairs and experts reached, scaled to the launches the trace holds."""

from rtbench import held


def read(run):
    return held.roofline(run, "decode", "moe_gmm_kernel", held.gmm_work(run))
