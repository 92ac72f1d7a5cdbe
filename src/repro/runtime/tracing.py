"""Named spans of the serving path, written into the profiler's own trace.

``span(name, **ids)`` is ``jax.profiler.TraceAnnotation``: with no profiler
session running it costs under a microsecond of host time and records
nothing; under ``jax.profiler.start_trace`` each span lands on the host
plane of the same trace, and on the same clock, as the device's
operations.  Request-scoped spans carry ``session=`` and ``round=``, step
spans ``rows=``.

:data:`SPANS` lists every name the program emits (a test holds the source
to it), so a trace reduction can tell program spans from anything else.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

SPANS = (
    # runtime (protocol.py): scheduling decisions
    "repro.route", "repro.order_queue", "repro.steal_scan",
    "repro.offload_scan",
    # remote prefill (LiveBackend.run_prefill, LivePrefillWorker.execute)
    "repro.prefill", "repro.kv.read_history", "repro.kv.insert_history",
    "repro.prefill.chunk", "repro.kv.extract", "repro.prefill.logits",
    # a remote increment joining its decode slot (LiveBackend.on_join)
    "repro.join", "repro.kv.attach",
    # decode worker steps (decode_once, fused_step) and their phases
    "repro.decode.step", "repro.fused", "repro.decode.feed",
    "repro.decode.launch", "repro.decode.wait", "repro.decode.sample",
    "repro.slot.reset",
)

#: ``with span(name, **ids):`` -- a host span named ``name`` (one of
#: :data:`SPANS`), with ``ids`` as its trace stats
span = TraceAnnotation
