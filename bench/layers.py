"""Shared arithmetic of the per-layer metric readers.

Names the trace carries today (a v5e trace, ``bench/testdata``): the
serving steps are jitted programs named after the engine's step functions
(``jit__step(<hash>)``, ``jit__pstep(<hash>)``), and each attention kernel
is a custom call named after its op wrapper (``%flash_attention.N``,
``%decode_attention.N``, ``%ragged_attention.N``).
"""
from __future__ import annotations

from typing import List, Optional, Sequence

from bench import trace, work
from bench.model import architecture

#: program (``XLA Modules``) names of the engine's model steps
STEP_PROGRAM = r"^jit__p?step\b"
#: operation names of the first attention kernels: the trace names an
#: operation by its HLO instruction (``%flash_attention.7 = ...
#: custom-call(...)``)
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


def kernel_roofline(run, op: str, kinds: Sequence[str],
                    work_fn: str) -> Optional[float]:
    """Roofline time of a kernel's calls in the traced window over the
    device time of the operations named by ``op``.  The calls are the
    recorded steps of the step kinds ``kinds`` (``probes.Call.kernel``);
    each one's least time is the sum, over the layers that run the kernel,
    of the roofline of ``work_fn`` of the configuration's architecture
    module (a layer that does not run it counts None)."""
    calls = [x for k in kinds for x in _traced_calls(run, kernel=k)]
    fn = getattr(architecture(run.config), work_fn, None) if calls else None
    if fn is None or run.trace.window is None:
        return None
    lo, hi = run.trace.window
    dev = trace.time_s(trace.matching(trace.all_ops(run.trace), op, lo, hi))
    if dev <= 0:
        return None
    need = 0.0
    for x in calls:
        for layer in range(run.config["num_hidden_layers"]):
            w = fn(run.config, x.segments, layer)
            if w is not None:
                need += work.roofline_s(*w, run.device_kind)
    return 100.0 * need / dev


def rounds_completed(run) -> int:
    """Rounds whose last token was emitted in the window."""
    return sum(1 for r in run.all_rounds
               if len(r.times) == r.output and run.w0 <= r.times[-1] < run.w1)


def per_step_ms(run, seconds: Sequence[str],
                steps: Sequence[str]) -> Optional[float]:
    """Milliseconds of the counters ``seconds`` per unit of the counters
    ``steps`` (each summed) over the window; None where ``steps`` did not
    move."""
    n = sum(run.delta(k) for k in steps)
    return 1e3 * sum(run.delta(k) for k in seconds) / n if n else None
