"""Roofline time of the flash-prefill kernel's calls in the traced window
(dense prefill and dense decode steps) over their device time."""
from bench.layers import kernel_roofline


def read(run):
    return kernel_roofline(run, "flash")
