"""Discrete event loop shared by the modeled and live runtimes (DESIGN.md §2).

One heap, one clock.  The modeled backend advances the clock by predicted
durations; the live backend advances it by wall-clock-measured engine times —
either way the protocol engine above sees the same ``at(t, fn)`` interface.
"""
from __future__ import annotations

import heapq
from typing import Callable, List, Optional, Tuple


class EventLoop:
    def __init__(self, max_time: float = float("inf")):
        self.now = 0.0
        self.max_time = max_time
        self._heap: List[Tuple[float, int, Callable[[], None], Optional[str]]] = []
        self._seq = 0

    def at(self, t: float, fn: Callable[[], None],
           label: Optional[str] = None) -> None:
        """Schedule ``fn`` at absolute time ``t`` (FIFO among equal times)."""
        self._seq += 1
        heapq.heappush(self._heap, (t, self._seq, fn, label))

    def after(self, dt: float, fn: Callable[[], None],
              label: Optional[str] = None) -> None:
        self.at(self.now + dt, fn, label)

    @property
    def pending(self) -> int:
        return len(self._heap)

    def run(self) -> float:
        """Drain the heap; returns the final clock value."""
        while self._heap:
            t, _, fn, _ = heapq.heappop(self._heap)
            if t > self.max_time:
                break
            self.now = max(self.now, t)
            fn()
        return self.now
