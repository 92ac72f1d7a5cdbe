"""Mean time a finished decode or fused step waited for the host before
the loop took its completion (program counters ``lag_s`` per ``steps`` of
the decode workers)."""
from bench.layers import per_step_ms

COUNTERS = ("decode.lag_s", "decode.steps")


def read(run):
    return per_step_ms(run, COUNTERS[:1], COUNTERS[1:])
