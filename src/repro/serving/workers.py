"""Live serving workers: real JAX execution behind the AMPD scheduler.

Each worker owns an :class:`Engine` (its mesh slice's jitted step fns).  The
cluster driver runs workers *logically in parallel*: every real engine call
is wall-clock timed and the measured duration advances that worker's logical
busy-time — so queueing, interference and SLOs behave exactly as on a real
deployment, just with CPU-scale models (reduced configs).

DecodeWorker implements TPU-style continuous batching with fixed slots: one
batched cache; empty slots decode a masked ``-1`` token (XLA static shapes).
A *local* prefill executes in-batch (one valid row, others masked), pausing
decoding for the measured duration — real PD interference, faithfully.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.types import PrefillTask, RoundSpec
from repro.models.packed import supports_packed
from repro.runtime.metrics import WindowStat
from repro.runtime.tracing import span
from repro.serving.engine import Engine, chunk_limit
from repro.serving.kv_transfer import (
    extract_range,
    insert_range,
    migrate_handoff,
    reshard,
    steal_handoff,
    transfer_bytes,
)


@dataclass
class LiveSession:
    session_id: int
    arrival_time: float
    rounds: List[RoundSpec]
    prompt_tokens: List[np.ndarray]          # per-round incremental tokens
    current_round: int = 0
    context_len: int = 0
    decode_worker: Optional[int] = None
    slot: Optional[int] = None
    tokens_this_round: int = 0
    last_token: int = 0
    last_token_time: float = 0.0
    generated: List[int] = field(default_factory=list)
    transcript: List[int] = field(default_factory=list)   # for failure replay
    ttfts: List[float] = field(default_factory=list)
    itls: List[float] = field(default_factory=list)
    finish_time: Optional[float] = None
    # -- multi-tenant SLO classes (DESIGN.md §19) -----------------------
    tenant: str = "default"
    trace: str = ""

    @property
    def num_rounds(self) -> int:
        return len(self.rounds)


def chunk_tokens_of(task: PrefillTask, session: LiveSession) -> np.ndarray:
    """The token slice a prefill task covers: the whole round increment for
    whole-task scheduling, or this sub-chunk's window under chunked prefill."""
    toks = session.prompt_tokens[task.round_idx]
    if task.incr_offset == 0 and task.l_incr >= len(toks):
        return toks
    return toks[task.incr_offset:task.incr_offset + task.l_incr]


def resolve_packed(cfg: ModelConfig, packed: Optional[bool]) -> bool:
    """Whether a decode worker runs the ragged packed fused path: ``None``
    follows the architecture; ``True`` on an arch without a ragged pack is
    refused rather than served dense."""
    if packed is None:
        return supports_packed(cfg)
    if packed and not supports_packed(cfg):
        raise ValueError(f"packed=True, but {cfg.name} has no ragged pack "
                         f"(layer pattern {cfg.layer_pattern})")
    return bool(packed)


def timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    jax.block_until_ready(jax.tree.leaves(out)[0])
    return time.perf_counter() - t0, out


def kv_op(worker, name: str, fn, *args):
    """One KV operation under the span ``name``, run to the completion of
    the tree it produces; its wall time adds to ``worker.kv_s``."""
    with span(name):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        worker.kv_s += time.perf_counter() - t0
    return out


def sample(logits) -> np.ndarray:
    """Greedy next tokens of a step's logits, on the host."""
    with span("repro.decode.sample"):
        return np.asarray(jnp.argmax(logits, axis=-1))


class WorkerSchedState:
    """The scheduling-facing worker surface — the ONLY fields the
    Coordinator and ServingRuntime read or write on a worker, shared by
    the in-process workers here and the proc-transport handles
    (``repro.serving.worker_proc``) so the duck-typed contract cannot
    drift between transports."""

    def _init_sched_state(self, idx: int, tp: int, window_s: float) -> None:
        self.idx = idx                  # STABLE id (never a list position)
        self.tp = tp
        self.speed = 1.0
        self.alive = True
        self.pclass = ""                # dedicated prefill class, "" = any (§19)
        self.prefill_queue: List[PrefillTask] = []
        self.ttft_stat = WindowStat(window_s)
        self.itl_stat = WindowStat(window_s)
        self.windowed_ttft = 0.0
        self.windowed_itl = 0.0
        self.busy_until = 0.0
        self.kv_bytes_moved = 0
        self.kv_s = 0.0                 # wall time of KV reads/inserts/extracts


class SlotBookkeeping:
    """Decode-slot occupancy owned by the coordinator side on BOTH
    transports (the proc worker's cache rows mirror it via ``reset_slot``
    RPCs).  Requires ``self.slots`` and ``self.reset_slot``."""

    def free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    def occupancy(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    def allocate(self, session: LiveSession) -> int:
        slot = self.free_slot()
        assert slot is not None, "no free decode slots"
        session.slot = slot
        self.slots[slot] = session
        self.reset_slot(slot)
        return slot

    def detach(self, session: LiveSession) -> None:
        if session.slot is not None:
            self.slots[session.slot] = None
            session.slot = None
        # cache row is wiped (reset_slot) on next allocate


class LivePrefillWorker(WorkerSchedState):
    kind = "prefill"

    def __init__(self, idx: int, engine: Engine, tp: int = 1,
                 window_s: float = 10.0):
        self._init_sched_state(idx, tp, window_s)
        self.engine = engine

    @property
    def cfg(self) -> ModelConfig:
        return self.engine.cfg

    def steal_handoff(self, task: PrefillTask,
                      session: Optional[LiveSession] = None) -> int:
        """A queued chunk migrated onto this worker (it is the thief):
        account the history payload it must now lazily re-read (§12)."""
        return steal_handoff(self.engine.cfg, task, session, None, self)

    def migrate_handoff(self, task: PrefillTask,
                        session: Optional[LiveSession] = None) -> int:
        """A local chunk was offloaded here from a saturated decode worker
        (§14): account the history payload this worker must lazily pull
        across the phase boundary before the chunk can run."""
        return migrate_handoff(self.engine.cfg, task, session, None, self)

    def execute(self, task: PrefillTask, session: LiveSession,
                history_extract: Optional[Dict] = None,
                cross_embeds=None) -> Dict[str, Any]:
        """Run one prefill task for real; returns the increment extract."""
        eng = self.engine
        tokens = chunk_tokens_of(task, session)
        if history_extract is not None and task.l_hist > 0:
            cache = kv_op(
                self, "repro.kv.insert_history", lambda: insert_range(
                    eng.new_cache(1), reshard(history_extract, eng.device),
                    eng.cfg, eng.max_len, 0, 0, replace_state=True))
            self.kv_bytes_moved += transfer_bytes(history_extract)
            lim = chunk_limit(eng.cfg, eng.max_len)
            logits = None
            for lo in range(0, len(tokens), lim):
                with span("repro.prefill.chunk"):
                    chunk = eng.pad_chunk(tokens[lo:lo + lim])
                    cache, logits, _ = eng.run_chunk(cache, chunk)
        else:
            with span("repro.prefill.chunk"):
                cache, logits = eng.prefill(tokens, cross_embeds=cross_embeds)
        # the extract queues behind the chunks, its dispatch hidden under
        # them; its KV time is what it adds once the logits are in hand
        with span("repro.kv.extract"):
            incr = extract_range(cache, eng.cfg, eng.max_len,
                                 task.l_hist, task.l_hist + task.l_incr)
        with span("repro.prefill.logits"):
            logits = np.asarray(logits)
        incr = kv_op(self, "repro.kv.extract", lambda: incr)
        self.kv_bytes_moved += transfer_bytes(incr)
        return {"increment": incr, "logits": logits}


class LiveDecodeWorker(WorkerSchedState, SlotBookkeeping):
    kind = "decode"

    def __init__(self, idx: int, engine: Engine, max_slots: int, tp: int = 1,
                 window_s: float = 10.0, chunk_tokens: int = 0,
                 packed: Optional[bool] = None):
        self._init_sched_state(idx, tp, window_s)
        self.engine = engine
        #: planner-chosen per-worker sub-chunk size (0 = runtime default);
        #: the ServingRuntime/Coordinator consult this at chunk boundaries
        self.chunk_tokens = chunk_tokens
        self.max_slots = max_slots
        self.cache = engine.new_cache(max_slots)
        self.slots: List[Optional[LiveSession]] = [None] * max_slots
        self.mem_tokens = 0
        #: ragged packed fused path (DESIGN.md §15): None = auto (on when the
        #: arch has a ragged pack)
        self.packed = resolve_packed(engine.cfg, packed)
        #: fused-step telemetry for LiveResult / fig14
        self.fused_steps = 0
        self.fused_s = 0.0
        #: wall time of decode/fused steps outside ``repro.decode.wait``:
        #: building the feed, dispatching, sampling
        self.host_s = 0.0
        #: occupied rows' cached context summed over dense decode steps:
        #: the cache positions the decode kernel reads (of max_slots x
        #: max_len per step)
        self.decode_ctx_tokens = 0

    # -- slot management (free/occupancy/allocate/detach: SlotBookkeeping) --
    def reset_slot(self, slot: int) -> None:
        """Wipe a slot's cache row (lengths, positions, state) before reuse —
        stale positions from a previous occupant must never look valid."""
        with span("repro.slot.reset"):
            fresh = self.engine.new_cache(1)
            self.cache = insert_range(self.cache, fresh, self.engine.cfg,
                                      self.engine.max_len, 0, slot,
                                      replace_state=True)

    def attach(self, session: LiveSession, increment: Dict, lo: int,
               first_token: int, n_tokens: int) -> None:
        """Insert a prefilled KV increment into this worker's batched cache.
        Memory accounting (``mem_tokens``) is owned by the ServingRuntime —
        uniform across local and remote placement."""
        if session.slot is None:
            self.allocate(session)
        eng = self.engine
        self.cache = kv_op(self, "repro.kv.attach", lambda: insert_range(
            self.cache, reshard(increment, eng.device), eng.cfg, eng.max_len,
            lo, session.slot, replace_state=True))
        session.last_token = first_token

    def history_extract(self, session: LiveSession) -> Dict:
        return extract_range(self.cache, self.engine.cfg, self.engine.max_len,
                             0, session.context_len, row=session.slot)

    def history_extract_range(self, session: LiveSession, lo: int,
                              hi: int) -> Dict:
        """Partial history pull (DESIGN.md §17): just the [lo, hi) miss
        suffix — the pool-resident prefix never crosses the wire."""
        return extract_range(self.cache, self.engine.cfg, self.engine.max_len,
                             lo, hi, row=session.slot)

    # -- execution ---------------------------------------------------------
    def _launch(self, step, *args):
        """Dispatch one step program and wait for every output; returns
        (outputs, seconds waited)."""
        with span("repro.decode.launch"):
            out = step(*args)
        with span("repro.decode.wait"):
            t0 = time.perf_counter()
            jax.block_until_ready(out)
            return out, time.perf_counter() - t0

    def decode_once(self):
        """One continuous-batching step over all occupied slots.

        Returns (duration_s, {slot: next_token}) — empty dict if idle.
        """
        occupied = [i for i, s in enumerate(self.slots) if s is not None]
        if not occupied:
            return 0.0, {}

        eng = self.engine
        with span("repro.decode.step", rows=len(occupied)):
            t0 = time.perf_counter()
            # the (B, 1) decode rectangle is only worth packing when
            # occupancy is low enough that the shape-bucketed stream is
            # strictly smaller — at full occupancy the rectangle is already
            # waste-free, while a pack pays bucket padding plus the
            # per-token row gather
            packed = False
            if self.packed:
                from repro.kernels.ragged_fused.ops import pack_layout
                _, total = pack_layout([1] * len(occupied), eng.pack_align)
                packed = eng.packed_bucket(total) < self.max_slots
            if packed:
                with span("repro.decode.feed"):
                    segs = [(i, np.asarray([self.slots[i].last_token],
                                           np.int32)) for i in occupied]
                (self.cache, logits, _), wait = self._launch(
                    eng.run_packed, self.cache, segs)
                rows = range(len(occupied))
            else:
                with span("repro.decode.feed"):
                    tokens = np.full((self.max_slots, 1), -1, np.int32)
                    for i in occupied:
                        tokens[i, 0] = self.slots[i].last_token
                    eng.tokens_uploaded += self.max_slots
                    tokens = jnp.asarray(tokens)
                    self.decode_ctx_tokens += sum(
                        self.slots[i].context_len for i in occupied)
                (self.cache, logits), wait = self._launch(
                    eng.decode_step, self.cache, tokens)
                rows = occupied
            dt = time.perf_counter() - t0
            nxt = sample(logits)
            self.host_s += time.perf_counter() - t0 - wait
        return dt, {slot: int(nxt[r]) for slot, r in zip(occupied, rows)}

    def local_prefill(self, task: PrefillTask, session: LiveSession):
        """Execute a prefill in-batch on this decode worker (pauses decode):
        a fused step with nobody piggybacking."""
        dt, first, _ = self.fused_step(task, session, [])
        return dt, first

    def fused_step(self, task: PrefillTask, session: LiveSession,
                   batch: List[LiveSession]):
        """Sarathi-style piggybacked step: prefill the chunk into the
        session's row while every decoding session's row carries its last
        token — one engine call advances both.  Per-row cache lengths make
        a 1-valid-token row behave exactly like a decode step; ``-1`` pads.

        Returns (duration_s, first_token_of_chunk, {session_id: next_token}).
        """
        eng = self.engine
        tokens = chunk_tokens_of(task, session)
        lim = chunk_limit(eng.cfg, eng.max_len)
        total_dt = total_wait = 0.0
        toks: Dict[int, int] = {}

        with span("repro.fused", session=task.session_id,
                  round=task.round_idx, rows=len(batch) + 1):
            t0 = time.perf_counter()
            for lo in range(0, len(tokens), lim):
                t1 = time.perf_counter()
                if self.packed:
                    # ragged path: the sub-chunk and the decode rows pack
                    # into one flat stream — chunk + batch tokens of
                    # compute, no rectangle.
                    with span("repro.decode.feed"):
                        segs = [(session.slot,
                                 np.asarray(tokens[lo:lo + lim], np.int32))]
                        if lo == 0:  # decode rows advance once per step
                            segs += [(s.slot,
                                      np.asarray([s.last_token], np.int32))
                                     for s in batch]
                    (self.cache, seg_logits, _), wait = self._launch(
                        eng.run_packed, self.cache, segs)
                    chunk_logits = seg_logits[0]
                    batch_logits = seg_logits[1:]
                    rows = range(len(batch))
                else:
                    with span("repro.decode.feed"):
                        sub = tokens[lo:lo + lim]
                        m = eng.pad_mult
                        width = ((len(sub) + m - 1) // m) * m
                        row = np.full((width,), -1, np.int32)
                        row[:len(sub)] = sub
                        feed = np.full((self.max_slots,), -1, np.int32)
                        if lo == 0:  # decode rows advance once per step
                            for s in batch:
                                feed[s.slot] = s.last_token
                        # non-advancing rows stay -1 in every sub-chunk: the
                        # matrix is composed on device from width +
                        # max_slots uploaded elements, never the max_slots x
                        # width rectangle (DESIGN.md §15).
                        c = eng.compose_fused_chunk(row, session.slot, feed)
                    (self.cache, logits, _), wait = self._launch(
                        eng.run_chunk, self.cache, c)
                    chunk_logits = logits[session.slot]
                    batch_logits = logits
                    rows = [s.slot for s in batch]
                total_dt += time.perf_counter() - t1
                total_wait += wait
                if lo == 0 and batch:
                    nxt = sample(batch_logits)
                    toks = {s.session_id: int(nxt[r])
                            for s, r in zip(batch, rows)}
            first = int(sample(chunk_logits))
            self.host_s += time.perf_counter() - t0 - total_wait
        self.fused_steps += 1
        self.fused_s += total_dt
        return total_dt, first, toks
