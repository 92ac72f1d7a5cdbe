"""Shared metric primitives for both runtime backends (DESIGN.md §4).

One percentile definition and one sliding-window estimator, so the modeled
simulator and the live cluster report *the same* statistics — previously
each path carried its own (diverging) copy of the percentile math.  The
global scheduling layer's counters (DESIGN.md §12) live here too: steal /
preempt events are part of the backend-parity contract surface, so both
backends must account them through the same structure.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass
from typing import Dict, List, Sequence


@dataclass
class SchedCounters:
    """Work-stealing / preemption accounting (DESIGN.md §12).

    Owned by the :class:`~repro.runtime.coordinator.Coordinator` — the only
    writer — and surfaced on both ``SimResult`` and ``LiveResult`` so the
    modeled and live backends report the new event kinds identically.
    """

    steals: int = 0            # queued chunks migrated to a draining worker
    preempts: int = 0          # parked remainders overtaken by higher priority
    # -- decode-local offload (DESIGN.md §14) ---------------------------
    migrations: int = 0        # local chunks shipped off a saturated decode
    offload_rejected: int = 0  # saturated scans where no move was profitable
    # -- global KV pool (DESIGN.md §17) ---------------------------------
    cache_hits: int = 0        # chunks that launched with a resident prefix
    cache_hit_tokens: int = 0  # sum of resident prefix tokens over those
    kv_spills: int = 0         # pages demoted HBM -> host tier
    kv_promotes: int = 0       # chunks whose plan promoted host-tier pages
    # -- elastic fleet autoscaling (DESIGN.md §18) ----------------------
    replans: int = 0           # lattice-cell adoptions (death/resize/drift)
    role_swaps: int = 0        # workers retired or spawned across replans


def p95(vals: Sequence[float]) -> float:
    """Upper empirical 95th percentile (nearest-rank, clamped)."""
    return quantile(vals, 0.95)


def quantile(vals: Sequence[float], q: float) -> float:
    if not vals:
        return 0.0
    s = sorted(vals)
    return s[min(len(s) - 1, int(q * len(s)))]


def mean(vals: Sequence[float]) -> float:
    return sum(vals) / len(vals) if vals else 0.0


def class_attainment(sessions: Sequence, slo) -> Dict[str, float]:
    """Per-tenant SLO attainment (prefill classing, DESIGN.md §19):
    tenant name -> fraction of its sessions that satisfied the spec.
    Judged by the same ``slo.satisfied`` as the aggregate number — which
    resolves per-tenant thresholds itself — so the per-class fractions
    always decompose the headline attainment exactly."""
    groups: Dict[str, List] = {}
    for s in sessions:
        groups.setdefault(getattr(s, "tenant", "default"), []).append(s)
    return {t: sum(1 for s in ss if slo.satisfied(s)) / len(ss)
            for t, ss in groups.items()}


class WindowStat:
    """Sliding-window mean over the last ``window_s`` seconds (paper §3).

    Drives the routing slack signals: every worker keeps one for TTFT and
    one for ITL, refreshed by the Coordinator before each routing decision.
    """

    def __init__(self, window_s: float = 10.0):
        self.window_s = window_s
        self.buf: deque = deque()

    def add(self, t: float, v: float) -> None:
        self.buf.append((t, v))

    def value(self, now: float) -> float:
        while self.buf and self.buf[0][0] < now - self.window_s:
            self.buf.popleft()
        if not self.buf:
            return 0.0
        return sum(v for _, v in self.buf) / len(self.buf)
