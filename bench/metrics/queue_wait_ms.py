"""Mean wait of queued work before the worker launched it (program
counters ``queue_wait_s`` per ``launches``, over every worker)."""
from bench.layers import per_step_ms

COUNTERS = ("prefill.queue_wait_s", "decode.queue_wait_s",
            "prefill.launches", "decode.launches")


def read(run):
    return per_step_ms(run, COUNTERS[:2], COUNTERS[2:])
