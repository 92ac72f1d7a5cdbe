"""Share of the traced window in which no operation ran on the device."""
from bench import trace


def read(run):
    if run.trace is None or run.trace.window is None or not run.trace.ops:
        return None
    lo, hi = run.trace.window
    return 100.0 * (1.0 - trace.busy_s(run.trace, lo, hi) / (hi - lo))
