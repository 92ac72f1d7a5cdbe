"""Roofline time of the flash-prefill kernel's calls in the traced window
(dense prefills and dense fused steps) over the device time of
``%flash_attention``."""
from bench.layers import KERNEL_OP, kernel_roofline


def read(run):
    return kernel_roofline(run, KERNEL_OP["flash"], ("flash",),
                           "attention_work")
