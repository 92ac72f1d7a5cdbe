"""KV bytes the prefill workers moved in the window (history reads and
increments, program counter ``kv_bytes_moved``), in MB per round completed
in the window."""
from bench.layers import rounds_completed

COUNTERS = ("prefill.kv_bytes_moved",)


def read(run):
    done = rounds_completed(run)
    return run.delta(COUNTERS[0]) / 1e6 / done if done else None
