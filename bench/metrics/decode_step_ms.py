"""Mean host-clock time of the decode worker's blocking decode steps
(``decode_once``) that ended in the window."""
from bench.layers import calls_in


def read(run):
    c = calls_in(run, run.w0, run.w1, origins=("decode_once",))
    return 1e3 * sum(x.t1 - x.t0 for x in c) / len(c) if c else None
