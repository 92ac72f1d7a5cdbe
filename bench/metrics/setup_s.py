"""Seconds from process start to the start of the measured window: weights,
cluster build, the engines' profiling, warm-up and the traffic's ramp."""


def read(run):
    return run.setup_s
