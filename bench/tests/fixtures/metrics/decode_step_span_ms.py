"""Test fixture metric: the mean length of the program's
``repro.decode.step`` spans in the traced window."""


def read(run):
    if run.trace is None or run.trace.window is None:
        return None
    lo, hi = run.trace.window
    d = [e - s for n, s, e in run.trace.spans
         if n == "repro.decode.step" and lo <= s < hi]
    return 1e3 * sum(d) / len(d) if d else None
