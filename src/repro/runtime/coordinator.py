"""Coordinator: binding, routing and queue ordering for BOTH runtimes
(paper §3 online stage; DESIGN.md §3).

This is the single authority over the paper's algorithms — ``route_prefill``
(Alg. 1) and ``reorder_queue`` (Alg. 2) have no other call site in the
serving paths.  Workers are duck-typed views exposing ``tp``, ``speed``,
``alive``, ``prefill_queue``, ``ttft_stat`` / ``itl_stat`` and
``windowed_ttft`` / ``windowed_itl``; the modeled simulator and the live
cluster both hand their workers straight in.

Slack signal (drain-aware, everywhere): a worker's windowed TTFT is the max
of its recent-completion window mean and its current queue-drain estimate
sum(T_pre over queued tasks).  Queue metadata is globally shared (§3) — the
single-controller adaptation of the paper's Redis layer — and without the
drain term a stale 10s window lets bursts pile onto one worker.

Global scheduling layer (DESIGN.md §12): with a :class:`StealingConfig`
attached, the Coordinator additionally (a) orders every queue by SLO-slack
priority — least laxity (deadline minus PerfModel service estimate) first —
instead of the per-queue Alg. 2 window, (b) records a *preempt* whenever a
higher-priority chunk overtakes a parked mid-round remainder at a chunk
boundary, and (c) plans *cross-worker steals*: when a prefill queue drains
below the watermark, ``plan_steal`` migrates the most profitable queued
chunk from the most backlogged worker — accepting a move only if the stay
ETA (victim drain + service there) exceeds the move ETA (thief drain +
service + the KV-locality penalty ``t_kv(l_hist)`` for re-reading history
on the thief).  Routing decisions are irrevocable at enqueue time;
stealing is the repair path when conditions drift (stragglers, bursts,
chunk remainders landing behind a backlog).

Decode-local offload (DESIGN.md §14): with an :class:`OffloadConfig`
attached, the Coordinator also repairs placements across the prefill/decode
phase boundary — the one direction stealing never touches.  When a decode
worker's projected stall (``T_fused`` over its running + queued local
chunks under the current decoding batch) exceeds the guard, ``plan_offload``
migrates queued local chunks to the most profitable prefill worker,
charging the full KV-locality penalty ``t_kv(l_hist)`` plus the increment
write-back that local execution gets for free; a schmitt-trigger hysteresis
band and a per-chunk migration budget keep the migrator from fighting the
router (oscillation is an explicitly tested failure mode).
"""
from __future__ import annotations

import random
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from repro.core.perf_model import PerfModel
from repro.core.reordering import reorder_queue
from repro.runtime.chunk_tuner import ChunkTuner
from repro.runtime.metrics import SchedCounters
from repro.core.routing import (
    RouteDecision,
    RoutingConfig,
    always_remote,
    class_eligible,
    route_prefill,
)
from repro.core.types import PrefillTask

COLOCATED = ("vllm", "continuum")
#: schedulers that run Alg. 2 reordering on every queue
REORDERING = ("ampd", "ampd-noroute", "ampd-chunked")
#: schedulers that run Alg. 1 adaptive routing
ADAPTIVE = ("ampd", "ampd-noreorder", "ampd-chunked")
SCHEDULERS = ("ampd", "ampd-noreorder", "ampd-noroute", "ampd-chunked",
              "dynamo", "vllm", "continuum")


@dataclass(frozen=True)
class OffloadConfig:
    """Decode-local offload knobs (DESIGN.md §14).

    Routing (Alg. 1) may deliberately place an incremental prefill *locally*
    on the bound decode worker — the KV-frugal choice — but the decision is
    irrevocable at enqueue time, and a burst of local chunks can saturate
    the decode side long after the router's window looked healthy.  With
    this config attached, the Coordinator re-visits those placements:
    whenever a decode worker's projected stall (``T_fused`` of the running
    plus queued local chunks under the current decoding batch) exceeds
    ``guard * itl_thres``, queued local chunks migrate to the most
    profitable prefill worker, paying the full KV-locality penalty
    ``t_kv(l_hist)`` plus the increment write-back they would have had for
    free locally.

    ``guard``: saturation trigger, as a multiple of the ITL SLO — the
    high-water mark of the schmitt trigger.
    ``hysteresis``: fraction of the trigger level the projected stall must
    drain below before the migrator disengages (the low-water mark); the
    [low, high] band is what keeps the migrator from fighting the router
    at the threshold.
    ``budget``: maximum times one chunk may migrate within its round — a
    chunk at budget stays put even under saturation (oscillation bound).
    ``min_profit_s``: required net ETA gain per migration (strict), as in
    :class:`StealingConfig`.
    """

    guard: float = 1.0
    hysteresis: float = 0.5
    budget: int = 1
    min_profit_s: float = 0.0


@dataclass(frozen=True)
class StealingConfig:
    """Knobs of the global scheduling layer (DESIGN.md §12).

    ``watermark``: a prefill worker whose queue length is at or below this
    looks for work to steal (0 = steal only when about to idle).
    ``min_profit_s``: required net ETA gain before a migration is accepted
    — the steal-profitability condition is strict, so marginal moves (which
    would just shuffle queue entries between equals) never happen.
    ``preemption``: enable SLO-slack priority ordering + preempt accounting
    (can be disabled to ablate stealing alone).
    """

    watermark: int = 0
    min_profit_s: float = 0.0
    preemption: bool = True


@dataclass
class Coordinator:
    perf: PerfModel
    routing: RoutingConfig
    scheduler: str = "ampd"
    reorder_w: int = 3
    seed: int = 0
    record_decisions: bool = False
    #: adaptive per-worker chunk sizing (DESIGN.md §11); when set, the
    #: runtime asks ``chunk_size`` at every chunk boundary instead of using
    #: a static chunk_tokens
    chunk_tuner: Optional[ChunkTuner] = None
    #: global scheduling layer (DESIGN.md §12): SLO-slack priority,
    #: chunk-boundary preemption and cross-worker work stealing
    stealing: Optional[StealingConfig] = None
    #: decode-local offload (DESIGN.md §14): migrate queued local prefill
    #: chunks off a saturated decode worker across the phase boundary
    offload: Optional[OffloadConfig] = None
    #: global KV pool (DESIGN.md §17): a runtime.kv_pool.PoolManager when
    #: pooling is on; CachePlans from it discount every history-read price
    pool_mgr: Optional[object] = None
    #: gate on the PRICING only — execution always honors resident pages,
    #: so cache_aware=False isolates the planning signal (the oracle
    #: suite's cache-blind arm) without changing what the workers do
    cache_aware: bool = True
    rng: random.Random = field(init=False)

    def __post_init__(self):
        if self.scheduler not in SCHEDULERS:
            raise ValueError(f"unknown scheduler {self.scheduler!r}; "
                             f"expected one of {SCHEDULERS}")
        self.rng = random.Random(self.seed)
        self.local_count = 0
        self.total_routed = 0
        self.rebinds = 0
        self.sched = SchedCounters()
        #: (session_id, round_idx, incr_offset, kind, worker_idx) per event,
        #: kind ∈ local | remote | steal | preempt | migrate | cache_hit |
        #: spill | promote | replan — the backend-parity contract surface
        #: (tests/test_runtime_unified, tests/test_multiproc_cluster).
        #: ``replan`` entries reuse the first three slots as
        #: (-1, fleet_size, load_bucket) since they are fleet-level, not
        #: per-chunk, decisions (DESIGN.md §18).
        self.decision_log: List[Tuple[int, int, int, str, Optional[int]]] = []

    # -- binding (§3 step 1) ----------------------------------------------
    def bind(self, session, decode_workers: List):
        """Least-loaded alive decode worker; prefers one with a free slot
        when workers expose slot admission (live continuous batching)."""
        alive = [d for d in decode_workers if d.alive]
        if not alive:
            raise RuntimeError(
                f"cannot bind session {session.session_id}: all "
                f"{len(decode_workers)} decode workers are dead — the "
                "runtime must drop (or queue) arrivals instead of binding")
        with_slot = [d for d in alive
                     if getattr(d, "free_slot", None) is None
                     or d.free_slot() is not None]
        d = min(with_slot or alive, key=lambda w: w.mem_tokens)
        session.decode_worker = d.idx
        return d

    # -- routing (§3 step 2 / §4.1) ---------------------------------------
    def refresh_stats(self, now: float, decode_worker, prefill_workers) -> None:
        """Drain-aware windowed stats, recomputed before every decision."""
        for w in list(prefill_workers) + [decode_worker]:
            drain = sum(self.perf.t_pre(k.l_hist, k.l_incr, w.tp, w.speed)
                        for k in w.prefill_queue)
            w.windowed_ttft = max(w.ttft_stat.value(now), drain)
            w.windowed_itl = w.itl_stat.value(now)

    def cache_plans(self, task: PrefillTask,
                    prefill_workers: List) -> Optional[Dict[int, object]]:
        """Per-candidate CachePlans for ``task``'s history read (DESIGN.md
        §17) — read-only pool walks, None when pooling is off, pricing is
        cache-blind, or there is no history to discount."""
        if (self.pool_mgr is None or not self.cache_aware
                or task.l_hist <= 0):
            return None
        return {w.idx: self.pool_mgr.plan_for(("prefill", w.idx),
                                              task.session_id, task.l_hist)
                for w in prefill_workers if getattr(w, "alive", True)}

    def note_cache(self, kind: str, task: PrefillTask, worker_idx: int,
                   tokens: int = 0) -> None:
        """Account a cache_hit / spill / promote event (DESIGN.md §17) —
        the PoolManager's emit hook, so pool decisions enter the same
        counters and decision log as routing decisions."""
        if kind == "cache_hit":
            self.sched.cache_hits += 1
            self.sched.cache_hit_tokens += tokens
        elif kind == "spill":
            self.sched.kv_spills += 1
        elif kind == "promote":
            self.sched.kv_promotes += 1
        if self.record_decisions:
            self.decision_log.append((task.session_id, task.round_idx,
                                      task.incr_offset, kind, worker_idx))

    def note_replan(self, fleet_size: int, bucket: int,
                    worker_idx: Optional[int], swaps: int = 0) -> None:
        """Account a FleetController plan swap (DESIGN.md §18).

        ``worker_idx`` is the stable id of the worker that triggered the
        swap (the dead worker on a death, the spawned worker on an explicit
        scale-up, -1 for load drift); ``swaps`` counts workers retired or
        spawned while converging to the adopted lattice cell.  Logged under
        session_id -1 so replay tooling can tell fleet-level events from
        per-chunk routing without a schema change."""
        self.sched.replans += 1
        self.sched.role_swaps += swaps
        if self.record_decisions:
            self.decision_log.append((-1, fleet_size, bucket, "replan",
                                      worker_idx))

    def route(self, task: PrefillTask, now: float, decode_worker,
              prefill_workers: List) -> RouteDecision:
        self.total_routed += 1
        self.refresh_stats(now, decode_worker, prefill_workers)
        plans = self.cache_plans(task, prefill_workers)

        if self.scheduler in COLOCATED or not prefill_workers:
            dec = RouteDecision("local", reason="colocated")
        elif self.scheduler in ("dynamo", "ampd-noroute"):
            dec = always_remote(task, decode_worker, prefill_workers,
                                self.perf, self.routing, self.rng,
                                plans=plans)
        else:  # ADAPTIVE: ampd / ampd-noreorder / ampd-chunked
            dec = route_prefill(task, decode_worker, prefill_workers,
                                self.perf, self.routing, self.rng,
                                plans=plans)
        if dec.kind == "local":
            self.local_count += 1
        if self.record_decisions:
            self.decision_log.append((task.session_id, task.round_idx,
                                      task.incr_offset, dec.kind,
                                      dec.worker_idx))
        return dec

    # -- chunk sizing (DESIGN.md §11) ---------------------------------------
    def chunk_size(self, task: PrefillTask, decode_worker,
                   decoding_batch: List, fallback: int) -> int:
        """Effective chunk size for splitting ``task``: the tuner's online
        derivation from the bound decode worker's current load when adaptive
        tuning is on, else the worker's planned per-group chunk_tokens, else
        the runtime-wide static value."""
        if self.chunk_tuner is not None:
            b = len(decoding_batch)
            avg_ctx = (sum(s.context_len for s in decoding_batch) / b
                       if b else 0.0)
            return self.chunk_tuner.chunk_for(
                decode_worker.tp, b, avg_ctx, task.l_hist,
                getattr(decode_worker, "speed", 1.0))
        return getattr(decode_worker, "chunk_tokens", 0) or fallback

    # -- global scheduling layer (DESIGN.md §12) ----------------------------
    @property
    def preemptive(self) -> bool:
        return self.stealing is not None and self.stealing.preemption

    def laxity(self, task: PrefillTask, worker, now: float) -> float:
        """SLO-slack priority: time to spare before this chunk must START to
        meet its round's deadline, priced by the PerfModel.  Lower = more
        urgent.  The deadline is the task's CLASS deadline (DESIGN.md §19):
        TTFT for round-0 first prompts, TTIT for incremental rounds — the
        pre-classing code priced every round against ttft_thres, so an
        urgent increment (tight TTIT, tiny T_pre) ordered behind any long
        first prompt that arrived earlier.  ``deadline - now - T_pre`` —
        the ordering between two tasks on one worker is independent of
        ``now`` (it cancels), which keeps the priority order identical
        across the modeled and live backends on the same queue state."""
        deadline = task.arrival_time + self.routing.deadline_for(task)
        return deadline - now - self.perf.t_pre(
            task.l_hist, task.l_incr, worker.tp, worker.speed)

    def note_parked(self, worker, chosen: PrefillTask, now: float) -> None:
        """Chunk-boundary preemption accounting: ``chosen`` was just popped;
        any queued mid-round remainder (incr_offset > 0) of another session
        with strictly more slack has had its continuation parked.  Counted
        once per chunk (the ``preempted`` flag) so repeated boundaries do
        not inflate the counter."""
        if not self.preemptive:
            return
        lx = self.laxity(chosen, worker, now)
        for k in worker.prefill_queue:
            if (k.incr_offset > 0 and not k.preempted
                    and k.session_id != chosen.session_id
                    and lx < self.laxity(k, worker, now)):
                k.preempted = True
                self.sched.preempts += 1
                if self.record_decisions:
                    self.decision_log.append(
                        (k.session_id, k.round_idx, k.incr_offset,
                         "preempt", worker.idx))

    def _plan(self, task: PrefillTask, prefill_worker):
        """Single-candidate CachePlan for the steal/offload profit gates
        (None when pooling is off or cache-blind)."""
        if self.pool_mgr is None or not self.cache_aware:
            return None
        return self.pool_mgr.plan_for(("prefill", prefill_worker.idx),
                                      task.session_id, task.l_hist)

    def plan_steal(self, thief, prefill_workers: List, now: float,
                   sessions: Dict[int, object], decode_workers: List):
        """Find the most profitable queued chunk to migrate onto ``thief``.

        Steal-profitability condition (strict): accept candidate ``k`` on
        victim ``v`` iff

            stay = drain(v ahead of k) + T_pre(k; v)
            move = drain(thief) + T_kv(l_hist; d -> thief) + T_pre(k; thief)
            stay - move > min_profit_s

        where the T_kv term is the KV-locality penalty — history must be
        re-read from the bound decode worker on the thief (and the lazy-read
        prefetch restarts, so the execution really pays it) — charged as 0
        when the session's chunk chain already lives on the thief.  A
        *running* task — on the victim AND on the thief (watermark>0
        prefetch steals while the thief still runs) — contributes its full
        service estimate to its side's drain (remaining time is unknowable
        live; the full estimate keeps the plan backend-deterministic).

        Returns (victim, task) or None.
        """
        st = self.stealing
        t_self = sum(self.perf.t_pre(k.l_hist, k.l_incr, thief.tp,
                                     thief.speed)
                     for k in thief.prefill_queue)
        mine = getattr(thief, "_rt_running_task", None)
        if mine is not None:
            t_self += self.perf.t_pre(mine.l_hist, mine.l_incr, thief.tp,
                                      thief.speed)
        best: Optional[Tuple[float, object, PrefillTask]] = None
        for v in prefill_workers:
            if v is thief or not v.alive or not v.prefill_queue:
                continue
            run = getattr(v, "_rt_running_task", None)
            ahead = (self.perf.t_pre(run.l_hist, run.l_incr, v.tp, v.speed)
                     if run is not None else 0.0)
            for k in v.prefill_queue:
                stay_run = self.perf.t_pre(k.l_hist, k.l_incr, v.tp, v.speed)
                s = sessions.get(k.session_id)
                if s is None or k.gen != getattr(s, "_rt_gen", 0):
                    continue                    # superseded by a rebind
                if not class_eligible(thief, k):
                    continue                    # class-dedicated pool (§19)
                move_read = 0.0
                if (k.l_hist > 0 and getattr(s, "_rt_chain_worker", None)
                        != ("prefill", thief.idx)):
                    # stable-id lookup: decode_worker is an id, NOT a list
                    # position (clusters may add/kill workers mid-run)
                    d = next(w for w in decode_workers
                             if w.idx == s.decode_worker)
                    move_read = self.perf.t_kv_read(
                        k.l_hist, d, thief, self._plan(k, thief))
                move = t_self + move_read + self.perf.t_pre(
                    k.l_hist, k.l_incr, thief.tp, thief.speed)
                profit = (ahead + stay_run) - move
                ahead += stay_run
                if profit > st.min_profit_s and (
                        best is None or profit > best[0]):
                    best = (profit, v, k)
        if best is None:
            return None
        _, victim, task = best
        self.sched.steals += 1
        if self.record_decisions:
            self.decision_log.append((task.session_id, task.round_idx,
                                      task.incr_offset, "steal", thief.idx))
        return victim, task

    # -- decode-local offload (DESIGN.md §14) -------------------------------
    def _stall_parts(self, decode_worker, decoding_batch: List):
        """Fused-step pricing of a decode worker's local prefill backlog
        under the CURRENT decoding batch: (running-task cost, [(chunk,
        cost) per queued chunk]).  One pass prices both the saturation
        signal and the per-chunk stay prefix — this runs at every decode
        kick."""
        b = len(decoding_batch)
        avg_ctx = (sum(s.context_len for s in decoding_batch) / b
                   if b else 0.0)
        est = lambda k: self.perf.t_fused(
            k.l_hist, k.l_incr, b, decode_worker.tp, avg_ctx,
            decode_worker.speed)
        run = getattr(decode_worker, "_rt_running_task", None)
        return (est(run) if run is not None else 0.0,
                [(k, est(k)) for k in decode_worker.prefill_queue])

    def projected_stall(self, decode_worker, decoding_batch: List) -> float:
        """Projected decode stall of ``decode_worker``: the time its local
        prefill backlog (the running task at its full estimate, plus every
        queued chunk) will occupy the engine, priced as fused steps under
        the CURRENT decoding batch — the ``T_fused`` family the planner and
        tuner already invert, so both backends project identically."""
        run_cost, queued = self._stall_parts(decode_worker, decoding_batch)
        return run_cost + sum(c for _k, c in queued)

    def plan_offload(self, decode_worker, prefill_workers: List, now: float,
                     sessions: Dict[int, object], decoding_batch: List):
        """Revisit Alg. 1 placements on a saturated decode worker: find one
        queued LOCAL chunk to migrate to the most profitable prefill worker
        (decode-local offload, DESIGN.md §14).

        Saturation is a schmitt trigger on :meth:`projected_stall`: engage
        above ``guard * itl_thres`` (high water), then keep migrating until
        the stall drains below ``hysteresis * guard * itl_thres`` (low
        water) — the band keeps a worker hovering at the threshold from
        shedding and re-accreting marginal chunks every boundary.  A chunk
        that has already migrated ``budget`` times this round stays put.

        Migration-profitability condition (strict): accept candidate ``k``
        for destination ``w`` iff

            stay = fused-drain(d ahead of k) + T_fused(k; d, batch)
            move = drain(w) + T_kv(l_hist; d -> w) + T_pre(k; w)
                   + T_kv(l_incr; w -> d)
            stay - move > min_profit_s

        The two T_kv terms are what local execution gets for free — the
        full KV-locality penalty of crossing the phase boundary: the
        destination must lazily re-read the history from ``decode_worker``
        AND write the increment back (charged 0 when the session's chunk
        chain already lives on ``w``).  Returns (task, dest) or None.
        """
        off = self.offload
        if off is None:
            return None
        # the guard protects the decoding batch's ITL: under per-tenant
        # classes the STRICTEST resident tenant's threshold governs (§19)
        itl = self.routing.itl_thres
        if self.routing.tenants and decoding_batch:
            itl = min(self.routing.itl_for(s) for s in decoding_batch)
        hi = off.guard * itl
        lo = off.hysteresis * hi
        run_cost, queued = self._stall_parts(decode_worker, decoding_batch)
        stall = run_cost + sum(c for _k, c in queued)
        hot = getattr(decode_worker, "_rt_offload_hot", False)
        if stall <= (lo if hot else hi):
            # below the governing water mark: disengage — evaluated even
            # with an empty queue, so a worker never stays "hot" across an
            # idle period and sheds the next lone chunk spuriously
            decode_worker._rt_offload_hot = False
            return None
        if not queued:
            return None        # stalled on the running task alone: nothing
        decode_worker._rt_offload_hot = True       # to shed
        # per-chunk stay costs are destination-independent: the single
        # _stall_parts pass above priced them once, not once per worker
        ahead = run_cost
        chunks: List[Tuple[PrefillTask, float, object]] = []
        examined = False
        for k, cost in queued:
            stay = ahead + cost
            ahead = stay
            s = sessions.get(k.session_id)
            if s is None or k.gen != getattr(s, "_rt_gen", 0):
                continue                # superseded by a rebind
            examined = True
            if k.migrations >= off.budget:
                continue                # oscillation bound: chunk is pinned
            chunks.append((k, stay, s))
        best: Optional[Tuple[float, PrefillTask, object]] = None
        for w in prefill_workers:
            if not w.alive:
                continue
            drain = sum(self.perf.t_pre(k.l_hist, k.l_incr, w.tp, w.speed)
                        for k in w.prefill_queue)
            mine = getattr(w, "_rt_running_task", None)
            if mine is not None:
                drain += self.perf.t_pre(mine.l_hist, mine.l_incr, w.tp,
                                         w.speed)
            for k, stay, s in chunks:
                if not class_eligible(w, k):
                    continue                # class-dedicated pool (§19)
                move_read = 0.0
                if (k.l_hist > 0 and getattr(s, "_rt_chain_worker", None)
                        != ("prefill", w.idx)):
                    move_read = self.perf.t_kv_read(
                        k.l_hist, decode_worker, w, self._plan(k, w))
                move = (drain + move_read
                        + self.perf.t_pre(k.l_hist, k.l_incr, w.tp, w.speed)
                        + self.perf.t_kv_between(k.l_incr, w, decode_worker))
                profit = stay - move
                if profit > off.min_profit_s and (
                        best is None or profit > best[0]):
                    best = (profit, k, w)
        if best is None:
            if examined:
                self.sched.offload_rejected += 1
            # nothing profitable (or every chunk at budget): disengage so
            # the scan does not re-run at every boundary while saturated
            decode_worker._rt_offload_hot = False
            return None
        _, task, dest = best
        self.sched.migrations += 1
        if self.record_decisions:
            self.decision_log.append((task.session_id, task.round_idx,
                                      task.incr_offset, "migrate", dest.idx))
        return task, dest

    # -- queue ordering (§4.2 / §12) ----------------------------------------
    def order_queue(self, worker, now: float) -> None:
        q = worker.prefill_queue
        if len(q) <= 1:
            return
        if self.preemptive:
            # SLO-slack priority: least laxity first; the sort is stable so
            # equal-laxity tasks keep FCFS order.  (now cancels in the
            # comparison — sort on the time-independent part.)  The
            # per-class deadline term no longer cancels across tasks of
            # different classes, so it stays in the key (DESIGN.md §19).
            q.sort(key=lambda t: t.arrival_time
                   + self.routing.deadline_for(t)
                   - self.perf.t_pre(t.l_hist, t.l_incr, worker.tp,
                                     worker.speed))
            # Overload refinement (§14, found by the scheduling-oracle
            # suite): pure least-laxity is longest-job-first among
            # near-equal arrivals — exactly inverted from the
            # satisfied-count-maximizing order once deadlines tighten, and
            # it cascades misses under overload.  Refine the head window
            # with Alg. 2 (starvation-bounded by ``postponements``); the
            # laxity sort still sets the macro order and the preemption
            # comparison in ``note_parked`` stays laxity-based.  Trade-off:
            # the refinement consults ``now - enqueue_time``, so — unlike
            # the bare laxity sort — the head order is only guaranteed
            # identical across the modeled and live backends on
            # protocol-determined traces (the kind every parity test and
            # golden pins); drift on timing-dependent traces is bounded to
            # the w-task window.
            est = lambda t: self.perf.t_pre(t.l_hist, t.l_incr, worker.tp,
                                            worker.speed)
            reorder_queue(q, now, self.routing.deadline_for, est,
                          self.reorder_w)
            return
        if self.scheduler in REORDERING:
            est = lambda t: self.perf.t_pre(t.l_hist, t.l_incr, worker.tp,
                                            worker.speed)
            reorder_queue(q, now, self.routing.deadline_for, est,
                          self.reorder_w)
        elif self.scheduler == "continuum":
            # session priority: tasks reusing cached KV first (stable)
            q.sort(key=lambda t: t.l_hist == 0)

    @property
    def local_fraction(self) -> float:
        return self.local_count / max(self.total_routed, 1)
