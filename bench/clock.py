"""The wall-clock event loop the benchmark gives the serving runtime.

The runtime's own loop is logical: its clock jumps from event to event, so
two workers whose calls ran one after the other on one host thread and one
chip look as if they had run side by side.  This loop keeps the runtime's
``at`` / ``after`` interface but ties ``now`` to the wall clock:

* an event due at ``t`` runs at ``max(t, wall)``; while nothing is due the
  loop sleeps;
* ``now`` is read from the wall clock when an event is popped, so a
  completion that ``after(duration, ...)`` schedules right after a blocking
  engine call lands at the wall time the call returned;
* work serialised on one thread or one chip therefore shows as real delay.
"""
from __future__ import annotations

import heapq
import time
from typing import Callable, List

from repro.runtime.events import EventLoop


class WallClockLoop(EventLoop):
    def __init__(self):
        super().__init__()
        self._t0 = time.perf_counter()
        #: seconds each arrival event ran after it was due
        self.arrival_lateness: List[float] = []

    def start(self) -> None:
        """Time zero of the traffic: now = 0 here."""
        self._t0 = time.perf_counter()
        self.now = 0.0

    def wall(self) -> float:
        return time.perf_counter() - self._t0

    def run_until(self, stop: Callable[[], bool], deadline: float) -> None:
        """Run due events until ``stop()`` holds or the wall clock passes
        ``deadline`` (seconds after ``start``)."""
        while self._heap and not stop():
            w = self.wall()
            if w >= deadline:
                return
            t = self._heap[0][0]
            if t > w:
                time.sleep(min(t, deadline) - w)
                continue
            t, _, fn, label = heapq.heappop(self._heap)
            self.now = self.wall()
            if label == "arrival":
                self.arrival_lateness.append(self.now - t)
            fn()

    def run(self) -> float:
        self.run_until(lambda: False, float("inf"))
        return self.now
