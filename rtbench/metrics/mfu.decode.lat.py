"""Useful FLOPs of the decode launches over their device time, percent
of the chip's peak."""

from rtbench import device


def read(run):
    return device.mfu(run, "decode")
