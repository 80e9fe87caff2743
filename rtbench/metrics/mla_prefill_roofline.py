"""The latent ragged prefill kernel's least time at the chip's peaks over
its device time, in percent, for the prefill launches the trace holds
(the reference's ``mla_prefill`` work)."""

from rtbench import held


def read(run):
    return held.roofline(run, "prefill", "mla_prefill_kernel",
                         run.work.get("mla_prefill"))
