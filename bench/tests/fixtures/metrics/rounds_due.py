"""Test fixture metric: the number of rounds due in the window."""


def read(run):
    return len(run.rounds)
