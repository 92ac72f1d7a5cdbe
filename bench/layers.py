"""Shared arithmetic of the per-layer metric readers.

Names the trace carries today (a v5e trace, ``bench/testdata``): the
serving steps are jitted programs named after the engine's step functions
(``jit__step(<hash>)``, ``jit__pstep(<hash>)``), and each attention kernel
is a custom call named after its op wrapper (``%flash_attention.N``,
``%ragged_attention.N``).
"""
from __future__ import annotations

from typing import List, Optional, Sequence

from bench import trace, work

#: program (``XLA Modules``) names of the engine's model steps
STEP_PROGRAM = r"^jit__p?step\b"
#: operation names of each attention kernel: the trace names an operation
#: by its HLO instruction (``%flash_attention.7 = ... custom-call(...)``)
KERNEL_OP = {"flash": r"^%flash_attention\b", "ragged": r"^%ragged_attention\b"}


def calls_in(run, lo: float, hi: float, origins: Sequence[str] = (),
             kernel: Optional[str] = None) -> List:
    return [x for x in run.recorder.calls
            if lo <= x.t0 and x.t1 <= hi
            and (not origins or x.origin in origins)
            and (kernel is None or x.kernel == kernel)]


def _traced_calls(run, **kw) -> List:
    if run.trace is None or run.trace_window is None:
        return []
    return calls_in(run, *run.trace_window, **kw)


def _logit_rows(call) -> int:
    return 1 if call.origin == "execute" else len(call.segments)


def step_mfu(run) -> Optional[float]:
    """Model FLOPs of every step the traced window ran, over the device
    time of the step programs in it, at the chip's bf16 peak."""
    calls = _traced_calls(run)
    if not calls or run.trace.window is None:
        return None
    lo, hi = run.trace.window
    dev = trace.time_s(trace.matching(trace.all_modules(run.trace),
                                      STEP_PROGRAM, lo, hi))
    if dev <= 0:
        return None
    flops = sum(work.step_flops(run.config, x.segments, _logit_rows(x))
                for x in calls)
    peak = work.peaks(run.device_kind)["bf16_flops"]
    return 100.0 * flops / (dev * peak)


def kernel_roofline(run, kernel: str) -> Optional[float]:
    calls = _traced_calls(run, kernel=kernel)
    if not calls or run.trace.window is None:
        return None
    lo, hi = run.trace.window
    ops = trace.matching(trace.all_ops(run.trace), KERNEL_OP[kernel], lo, hi)
    dev = trace.time_s(ops)
    if dev <= 0:
        return None
    L = run.config["num_hidden_layers"]
    need = sum(work.roofline_s(*(L * v for v in work.attention_work(
        run.config, x.segments)), run.device_kind) for x in calls)
    return 100.0 * need / dev
