"""Wall time of the KV operations in the window (history reads and
inserts, increment extracts and attaches; program counter ``kv_s`` over
every worker), in ms per round completed in the window."""
from bench.layers import rounds_completed

COUNTERS = ("prefill.kv_s", "decode.kv_s")


def read(run):
    done = rounds_completed(run)
    return 1e3 * sum(run.delta(k) for k in COUNTERS) / done if done else None
