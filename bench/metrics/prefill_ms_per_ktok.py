"""Prefill workers' busy time in the window (program counter
``util_busy_s``) per thousand prompt tokens they processed."""
from bench.layers import calls_in

COUNTERS = ("prefill.util_busy_s",)


def read(run):
    toks = sum(s[0] for x in calls_in(run, run.w0, run.w1,
                                      origins=("execute",))
               for s in x.segments)
    return 1e3 * run.delta(COUNTERS[0]) / (toks / 1e3) if toks else None
