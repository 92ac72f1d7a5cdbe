"""Reduction of a profiler trace to device times.

A trace (``*.xplane.pb``) holds one plane per device, with a line of
operations (``XLA Ops``) and a line of whole programs (``XLA Modules``),
and a host plane whose thread lines carry the benchmark's own spans
(``bench:window`` around the traced window, ``bench:<call>`` around each
worker call) and the program's (the names in
``repro.runtime.tracing.SPANS``).  Times are in seconds on the trace's one
clock.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Span = Tuple[str, float, float]          # (name, start_s, end_s)

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench:window"


@dataclass
class Trace:
    #: device plane name -> operations / programs on it
    ops: Dict[str, List[Span]] = field(default_factory=dict)
    modules: Dict[str, List[Span]] = field(default_factory=dict)
    host: List[Span] = field(default_factory=list)      # bench:* spans
    spans: List[Span] = field(default_factory=list)     # the program's

    @property
    def window(self) -> Optional[Tuple[float, float]]:
        w = [(s, e) for n, s, e in self.host if n == WINDOW_SPAN]
        return (min(s for s, _ in w), max(e for _, e in w)) if w else None


def find_xplane(directory: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    # ``repro.runtime`` imports cleanly only after ``repro.core`` (a
    # circular import between the two packages otherwise)
    import repro.core  # noqa: F401
    from repro.runtime.tracing import SPANS
    program = set(SPANS)
    data = ProfileData.from_file(path)
    tr = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    spans = [(e.name, e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9)
                             for e in line.events]
                    dest = tr.ops if line.name == OPS_LINE else tr.modules
                    dest.setdefault(plane.name, []).extend(spans)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    dest = (tr.host if e.name.startswith("bench:") else
                            tr.spans if e.name in program else None)
                    if dest is not None:
                        dest.append((e.name, e.start_ns * 1e-9,
                                     (e.start_ns + e.duration_ns) * 1e-9))
    return tr


def union_s(spans: Sequence[Span], lo: float, hi: float) -> float:
    """Length of the union of the spans, clipped to [lo, hi]."""
    iv = sorted((max(s, lo), min(e, hi)) for _, s, e in spans
                if e > lo and s < hi)
    total, cur_s, cur_e = 0.0, None, None
    for s, e in iv:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_s(tr: Trace, lo: float, hi: float) -> float:
    """Device-busy seconds in [lo, hi], averaged over the devices traced."""
    if not tr.ops:
        return 0.0
    return sum(union_s(v, lo, hi) for v in tr.ops.values()) / len(tr.ops)


def matching(spans: Sequence[Span], pattern: str, lo: float,
             hi: float) -> List[Span]:
    rx = re.compile(pattern)
    return [s for s in spans if rx.search(s[0]) and s[1] >= lo and s[1] < hi]


def all_ops(tr: Trace) -> List[Span]:
    return [s for v in tr.ops.values() for s in v]


def all_modules(tr: Trace) -> List[Span]:
    return [s for v in tr.modules.values() for s in v]


def time_s(spans: Sequence[Span]) -> float:
    return sum(e - s for _, s, e in spans)


def op_label(name: str) -> str:
    """``%fusion.143 = (bf16[...]) fusion(...)`` -> ``fusion``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def top_ops(spans: Sequence[Span], n: int = 10) -> List[List]:
    """Device seconds per operation kind, the largest first.  A loop
    operation (``while``) spans the operations it runs, so it counts them
    again: it stands for the whole scan over layers."""
    agg: Dict[str, float] = {}
    for name, s, e in spans:
        k = op_label(name)
        agg[k] = agg.get(k, 0.0) + (e - s)
    return [[k, v] for k, v in sorted(agg.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: Trace, lo: float, hi: float, n: int = 10) -> List[List]:
    """The longest gaps with no device operation, each named by what the
    host was doing at the gap's middle: the program span covering it that
    started last, else the benchmark's span that did, else ``host``."""
    ops = sorted((s, e) for _, s, e in all_ops(tr) if e > lo and s < hi)
    gaps, cur = [], lo
    for s, e in ops:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    host = [h for h in tr.host if h[0] != WINDOW_SPAN]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        name = "host"
        for spans in (host, tr.spans):
            cover = [h for h in spans if h[1] <= mid < h[2]]
            if cover:
                name = max(cover, key=lambda h: h[1])[0]
        out.append([name, e - s])
    return out
