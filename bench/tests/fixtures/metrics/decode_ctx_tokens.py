"""Test fixture metric: a program counter that no harness file names
(``decode_ctx_tokens`` of the decode workers) over the window."""

COUNTERS = ("decode.decode_ctx_tokens",)


def read(run):
    return run.delta(COUNTERS[0])
