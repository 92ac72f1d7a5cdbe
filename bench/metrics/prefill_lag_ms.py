"""Mean time a finished prefill waited for the host before the loop took
its completion (program counters ``lag_s`` per ``steps`` of the prefill
workers)."""
from bench.layers import per_step_ms

COUNTERS = ("prefill.lag_s", "prefill.steps")


def read(run):
    return per_step_ms(run, COUNTERS[:1], COUNTERS[1:])
