"""Mean host time of a decode worker's step outside its wait for the
device: building the feed, dispatching, sampling (program counters
``host_s`` per ``steps`` of the decode workers)."""
from bench.layers import per_step_ms

COUNTERS = ("decode.host_s", "decode.steps")


def read(run):
    return per_step_ms(run, COUNTERS[:1], COUNTERS[1:])
