"""Harness-side records around the calls into each layer of the program.

The benchmark wraps the runtime's backend and the workers' public calls on
the instances it built; it edits no program code.  It records

* each round's first token and every later token, with the wall time at
  which the runtime handed it over (the user-visible emission time);
* each worker call: which step it was, its host-clock span, and the work
  it asked for (rows, new tokens, cached context per row), from which
  ``bench.work`` counts operations and bytes;
* with a profiler running, a named span around every call, so the trace
  reduction can attribute device time to the call that caused it.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

import jax


@dataclass
class Call:
    origin: str          # worker call: "execute" | "decode_once" | "fused_step"
    #: the step's kind, named by the attention kernel it runs: "flash" (a
    #: dense prefill or fused step), "decode" (a dense decode step, one
    #: query per row), "ragged" (a packed step)
    kernel: str
    t0: float            # loop seconds
    t1: float
    segments: List[Tuple[int, int]]   # (new tokens, cached context) per row


@dataclass
class RoundLog:
    first: Optional[Tuple[float, int]] = None      # (time, token)
    tokens: List[Tuple[float, int]] = field(default_factory=list)


class Recorder:
    def __init__(self, loop):
        self.loop = loop
        self.rounds: Dict[Tuple[int, int], RoundLog] = {}
        self.calls: List[Call] = []
        self.local_rounds: Set[Tuple[int, int]] = set()   # prefilled locally
        self.annotate = False

    def _round(self, sid: int, r: int) -> RoundLog:
        log = self.rounds.get((sid, r))
        if log is None:
            log = self.rounds[(sid, r)] = RoundLog()
        return log

    # -- tokens ---------------------------------------------------------
    def install(self, cluster) -> None:
        be = cluster.runtime.backend
        on_join, on_token = be.on_join, be.on_token

        def join(d, s, task, payload):
            on_join(d, s, task, payload)
            if task.is_final_chunk:
                self._round(s.session_id, task.round_idx).first = (
                    self.loop.now, int(s.last_token))

        def token(d, s, tok):
            on_token(d, s, tok)
            self._round(s.session_id, s.current_round).tokens.append(
                (self.loop.now, int(tok)))

        be.on_join, be.on_token = join, token
        for w in cluster.prefill_workers:
            self._wrap_prefill(w)
        for w in cluster.decode_workers:
            self._wrap_decode(w)

    # -- calls ----------------------------------------------------------
    def _span(self, name: str, fn, *args):
        t0 = self.loop.wall()
        if self.annotate:
            with jax.profiler.TraceAnnotation(name):
                out = fn(*args)
        else:
            out = fn(*args)
        return t0, self.loop.wall(), out

    def _wrap_prefill(self, w) -> None:
        execute = w.execute

        def run(task, session, history_extract=None, cross_embeds=None):
            hist = task.l_hist if history_extract is not None else 0
            t0, t1, out = self._span(
                "bench:prefill", execute, task, session, history_extract)
            self.calls.append(Call("execute", "flash", t0, t1,
                                   [(task.l_incr, hist)]))
            return out

        w.execute = run

    def _wrap_decode(self, w) -> None:
        decode_once, fused_step = w.decode_once, w.fused_step
        eng = w.engine

        def decode():
            rows = [(1, s.context_len) for s in w.slots if s is not None]
            t0, t1, out = self._span("bench:decode", decode_once)
            if rows:
                from repro.kernels.ragged_fused.ops import pack_layout
                _, total = pack_layout([1] * len(rows), eng.pack_align)
                packed = (w.packed
                          and eng.packed_bucket(total) < w.max_slots)
                self.calls.append(Call("decode_once",
                                       "ragged" if packed else "decode",
                                       t0, t1, rows))
            return out

        def fused(task, session, batch):
            segs = [(task.l_incr, task.l_hist)]
            segs += [(1, s.context_len) for s in batch]
            t0, t1, out = self._span("bench:fused", fused_step, task,
                                     session, batch)
            self.calls.append(Call("fused_step",
                                   "ragged" if w.packed else "flash",
                                   t0, t1, segs))
            self.local_rounds.add((task.session_id, task.round_idx))
            return out

        w.decode_once, w.fused_step = decode, fused

