"""Backend compilations (``jax.monitoring``) inside the measured window."""


def read(run):
    return sum(1 for t in run.compile_times if run.w0 <= t < run.w1)
