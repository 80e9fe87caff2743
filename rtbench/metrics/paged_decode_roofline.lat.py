"""The paged decode kernel's least time at the chip's peaks over its
device time, in percent."""

from rtbench import device


def read(run):
    return device.roofline(run, "decode", "paged_decode_kernel")
