"""Roofline time of the decode kernel's calls in the traced window (dense
decode steps: each row's live K/V read once, its q read and its output
written) over the device time of ``%decode_attention``."""
from bench.layers import kernel_roofline

OP = r"^%decode_attention\b"


def read(run):
    return kernel_roofline(run, OP, ("decode",), "decode_attention_work")
