"""Output tokens emitted in the window, over the window's seconds."""


def read(run):
    n = sum(1 for r in run.all_rounds for t in r.times if run.w0 <= t < run.w1)
    return n / (run.w1 - run.w0)
