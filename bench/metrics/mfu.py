"""Model FLOPs the steps in the traced window needed, over the device time
of the step programs (both workers) at the chip's bf16 peak."""
from bench.layers import step_mfu


def read(run):
    return step_mfu(run)
