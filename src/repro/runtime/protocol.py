"""ServingRuntime: the multi-round serving protocol as ONE state machine
(DESIGN.md §2).

arrival -> bind -> [chunk split] -> route -> prefill queue -> (lazy history
read | execute | KV write-back) -> join decode batch -> continuous decode ->
round complete -> env delay -> next-round increment -> ... -> finish; plus
worker failure -> rebind -> context re-prefill, stragglers and elastic
scale-up.  The paper's Alg. 1 / Alg. 2 run inside the :class:`Coordinator`;
durations and tokens come from the pluggable :class:`ExecutionBackend` —
the discrete-event simulator and the live JAX cluster are the SAME engine
with different backends.

Chunked incremental prefill (DESIGN.md §7): with ``chunk_tokens`` set
(implied by the ``ampd-chunked`` scheduler), each round's increment is split
into sub-chunks that are routed and reordered independently; decode steps
interleave at chunk boundaries so a local prefill pauses the decode batch
for at most one chunk, and a remote chunk's KV is written back eagerly so
the next chunk may run anywhere (history stays lazily readable).

Chunk sizing is re-derived at EVERY chunk boundary (DESIGN.md §11): the
runtime splits off only the next sub-chunk and keeps the remainder as one
pending task, asking the Coordinator for the effective size each time — a
planner-chosen per-worker ``chunk_tokens`` (carried on the worker), or the
:class:`~repro.runtime.chunk_tuner.ChunkTuner`'s online derivation from the
bound decode worker's current batch/context, or the static runtime-wide
value.  With a static size this reproduces exactly the old up-front split.

Global scheduling layer (DESIGN.md §12): with a ``StealingConfig`` on the
Coordinator, queues order by SLO-slack priority, a higher-priority chunk
overtaking a parked mid-round remainder at a chunk boundary is accounted as
a *preemption* (no mid-kernel aborts — the remainder simply waits), and a
prefill worker whose queue drains below the watermark *steals* the most
profitable queued chunk from the most backlogged worker (``plan_steal``
charges the KV-locality penalty before accepting).  A stolen task's
``enqueue_time`` resets so the lazy-read prefetch overlap restarts on the
thief — the penalty the Coordinator priced is the one the execution pays.

Decode-local offload (DESIGN.md §14): with an ``OffloadConfig`` on the
Coordinator, every kick of a decode worker revisits the Alg. 1 placement of
its queued LOCAL chunks — the one repair direction stealing cannot reach.
When the projected stall (``T_fused`` over running + queued chunks under
the current batch) trips the guard, queued chunks migrate to prefill
workers (``migrate`` decision events), paying the full KV-locality penalty;
their parked ``_rt_rest`` remainders re-dispatch at join time through the
normal routing path, crossing the phase boundary with them.

Session objects are duck-typed (core ``Session`` or serving ``LiveSession``)
and gain runtime-managed fields: ``state`` ∈ arriving | prefill_wait |
decoding | env | done | dropped, a rebind generation counter (stale events
from before a failure are dropped), and per-round token counters.  The
runtime owns ALL memory accounting: ``mem_tokens`` += l_incr on join, += 1
per decoded token, -= context_len on detach — so a decode worker's counter
provably returns to 0 once its sessions leave.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional

from repro.core.types import PrefillTask
from repro.runtime.backend import ExecutionBackend, WorkerDiedError
from repro.runtime.coordinator import Coordinator
from repro.runtime.events import EventLoop
from repro.runtime.tracing import span

#: default sub-chunk size when the ampd-chunked scheduler is selected
#: without an explicit chunk_tokens (≈ one decode-step-bounded pause)
DEFAULT_CHUNK_TOKENS = 512


class ServingRuntime:
    def __init__(self, backend: ExecutionBackend, coordinator: Coordinator,
                 prefill_workers: List, decode_workers: List, *,
                 chunk_tokens: int = 0, max_time: float = float("inf"),
                 admission_retry_s: float = 0.05):
        self.backend = backend
        self.coordinator = coordinator
        self.prefill_workers = prefill_workers
        self.decode_workers = decode_workers
        self.events = EventLoop(max_time)
        self.sessions: Dict[int, object] = {}   # id -> session (never index)
        self.admission_retry_s = admission_retry_s
        self.chunk_tokens = chunk_tokens or (
            DEFAULT_CHUNK_TOKENS if coordinator.scheduler == "ampd-chunked"
            else 0)
        #: optional FleetController (DESIGN.md §18) — set by the owning
        #: facade when autoscaling is enabled
        self.fleet = None
        self._spawn_seq = 0         # monotonic worker-incarnation counter
        for w in list(prefill_workers) + list(decode_workers):
            self._init_worker(w)
        self._chunked = bool(
            self.chunk_tokens
            or coordinator.chunk_tuner is not None
            or any(getattr(w, "chunk_tokens", 0) for w in decode_workers))

    # -- wiring ------------------------------------------------------------
    @property
    def now(self) -> float:
        return self.events.now

    def worker_by_id(self, kind: str, idx: int):
        """Resolve a worker by its STABLE id, never by list position —
        clusters that add/kill workers mid-run must not cross wires."""
        ws = self.prefill_workers if kind == "prefill" else self.decode_workers
        for w in ws:
            if w.idx == idx:
                return w
        return None

    def _bound_decode(self, s):
        d = self.worker_by_id("decode", s.decode_worker)
        assert d is not None, (
            f"session {s.session_id} bound to unknown decode worker "
            f"{s.decode_worker}")
        return d

    def _init_worker(self, w) -> None:
        w._running = False
        w._rt_running_task = None       # in-flight prefill (steal planning)
        w._rt_offload_hot = False       # offload schmitt trigger state (§14)
        if not hasattr(w, "util_busy_s"):
            w.util_busy_s = 0.0
        # launches of queued work and the queue wait before each; completed
        # steps and how long each completion ran past its due time
        w.launches, w.queue_wait_s = 0, 0.0
        w.steps, w.lag_s = 0, 0.0
        if not hasattr(w, "tasks_done"):
            w.tasks_done = 0
        if not hasattr(w, "chunk_tokens"):
            w.chunk_tokens = 0          # planner-chosen per-worker size
        # incarnation stamp: a scheduled failure is aimed at the worker
        # that held the id at schedule time, never at a later same-id
        # replacement (generation guard, DESIGN.md §18)
        self._spawn_seq += 1
        w._rt_spawn_gen = self._spawn_seq

    def register_worker(self, w, kind: str):
        """Elastic scale-up: add a worker mid-run; it starts pulling work on
        the next routing decision — or immediately, by stealing backlog."""
        ws = self.prefill_workers if kind == "prefill" else self.decode_workers
        ws.append(w)
        self._init_worker(w)
        if kind == "decode" and getattr(w, "chunk_tokens", 0):
            self._chunked = True
        if kind == "prefill":
            self._kick(w)               # empty queue -> steal attempt
        return w

    def submit(self, session) -> None:
        self.sessions[session.session_id] = session
        session.state = "arriving"
        session.tokens_this_round = 0
        session.last_token_time = 0.0
        session._rt_gen = 0
        session._rt_rest = None
        session._rt_chain_worker = None
        self.events.at(session.arrival_time,
                       lambda s=session: self._on_arrival(s), "arrival")

    def schedule_failure(self, kind: str, idx: int, at: float) -> None:
        # capture the current incarnation of the id: a worker spawned
        # later (even at the same logical time) under the same stable id
        # must not inherit this scheduled death
        w = self.worker_by_id(kind, idx)
        gen = None if w is None else w._rt_spawn_gen
        self.events.at(at, lambda: self._on_failure(kind, idx, spawn_gen=gen),
                       "failure")

    def retire_worker(self, kind: str, idx: int) -> None:
        """Graceful decommission by stable id (fleet swaps, DESIGN.md §18):
        same recovery machinery as a failure — queued chunks re-route,
        decode residents rebind — but announced by the FleetController
        rather than discovered, so no replan trigger re-fires."""
        self._on_failure(kind, idx)

    def run(self) -> float:
        return self.events.run()

    # -- arrival & binding (§3 step 1) -------------------------------------
    def _on_arrival(self, s) -> None:
        if self.fleet is not None:
            self.fleet.on_arrival(self.now)   # rate estimator / drift swap
        if not any(d.alive for d in self.decode_workers):
            s.state = "dropped"
            return
        self.coordinator.bind(s, self.decode_workers)
        task = PrefillTask(
            session_id=s.session_id, round_idx=0, l_hist=0,
            l_incr=self.backend.incr_len(s, 0), enqueue_time=self.now,
            arrival_time=self.now, is_initial=True, gen=s._rt_gen,
            tenant=getattr(s, "tenant", "default"))
        self._dispatch(s, task)

    # -- dispatch: chunk split + routing (§3 step 2 / §4.1) -----------------
    def _dispatch(self, s, task: PrefillTask) -> None:
        """Route the next unit of work; in chunked mode, split off one
        sub-chunk sized for CURRENT conditions and park the remainder
        (re-split at the next boundary — DESIGN.md §11)."""
        if s.state == "dropped":
            return
        rest, s._rt_rest = s._rt_rest, None
        if (rest is not None and rest.gen == task.gen
                and rest.round_idx == task.round_idx
                and rest.incr_offset == task.incr_offset + task.l_incr):
            # re-dispatch of a chunk whose remainder is still parked (its
            # prefill worker died while the chunk was queued): reabsorb the
            # remainder so no increment tokens are lost — re-split below
            task = PrefillTask(
                session_id=task.session_id, round_idx=task.round_idx,
                l_hist=task.l_hist, l_incr=task.l_incr + rest.l_incr,
                enqueue_time=task.enqueue_time,
                arrival_time=task.arrival_time, is_initial=task.is_initial,
                incr_offset=task.incr_offset,
                is_final_chunk=rest.is_final_chunk, gen=task.gen,
                tenant=task.tenant)
        if self._chunked:
            d = self._bound_decode(s)
            batch = []
            if self.coordinator.chunk_tuner is not None:
                # only the tuner reads the current decoding batch
                batch = [b for b in self.backend.attached(d)
                         if getattr(b, "state", "") == "decoding"]
            c = self.coordinator.chunk_size(task, d, batch, self.chunk_tokens)
            if c and task.l_incr > c:
                task, s._rt_rest = self._split_task(task, c)
        self._route_one(s, task)

    @staticmethod
    def _split_task(task: PrefillTask, c: int):
        """(first c tokens, remainder) of one increment task."""
        first = PrefillTask(
            session_id=task.session_id, round_idx=task.round_idx,
            l_hist=task.l_hist, l_incr=c,
            enqueue_time=task.enqueue_time, arrival_time=task.arrival_time,
            is_initial=task.is_initial, incr_offset=task.incr_offset,
            is_final_chunk=False, gen=task.gen, tenant=task.tenant)
        rest = PrefillTask(
            session_id=task.session_id, round_idx=task.round_idx,
            l_hist=task.l_hist + c, l_incr=task.l_incr - c,
            enqueue_time=task.enqueue_time, arrival_time=task.arrival_time,
            is_initial=task.is_initial, incr_offset=task.incr_offset + c,
            is_final_chunk=task.is_final_chunk, gen=task.gen,
            tenant=task.tenant)
        return first, rest

    def _route_one(self, s, task: PrefillTask) -> None:
        d = self._bound_decode(s)
        if not d.alive:
            self._rebind(s, task)
            return
        # full list: Alg. 1 skips dead/ineligible workers itself; the
        # decision names its worker by STABLE id
        with span("repro.route", session=task.session_id,
                  round=task.round_idx):
            dec = self.coordinator.route(task, self.now, d,
                                         self.prefill_workers)
        task.enqueue_time = self.now
        s.state = "prefill_wait"
        if dec.kind == "local":
            try:
                admitted = self.backend.admit_local(d, s)
            except WorkerDiedError as e:
                self._on_rpc_death(e, d, task, s)
                return
            if not admitted:
                # admission backpressure: retry shortly (a slot frees when a
                # resident session finishes)
                self.events.after(
                    self.admission_retry_s,
                    lambda: (task.gen == s._rt_gen
                             and self._route_one(s, task)),
                    "admission-retry")
                return
            task.routed_to = "local"
            d.prefill_queue.append(task)
            self._kick(d)
        else:
            # resolve by stable id: an autoscaler hot swap may have
            # reordered prefill_workers since the decision was priced
            w = self.worker_by_id("prefill", dec.worker_idx)
            assert w is not None, f"routed to unknown worker {dec.worker_idx}"
            task.routed_to = f"remote:{w.idx}"
            w.prefill_queue.append(task)
            self._kick(w)
            self._steal_scan()          # drained peers may relieve w

    # -- worker advance: prefill first (priority), else decode --------------
    def _kick(self, w) -> None:
        if not w.alive:
            return
        if w.kind == "decode":
            # decode-local offload (§14): every kick of a decode worker —
            # an enqueue or a chunk boundary — revisits the Alg. 1
            # placement of its queued local chunks.  Runs even while the
            # worker executes: queued chunks can leave mid-step.
            self._try_offload(w)
        if w._running:
            return
        while w.prefill_queue:
            with span("repro.order_queue"):
                self.coordinator.order_queue(w, self.now)
            task = w.prefill_queue.pop(0)
            s = self.sessions[task.session_id]
            if task.gen != s._rt_gen:       # superseded by a rebind
                continue
            # chunk-boundary preemption accounting: queued remainders with
            # more slack than the chosen chunk just got parked (§12)
            self.coordinator.note_parked(w, task, self.now)
            d = self._bound_decode(s)
            if w.kind == "decode" and self._chunked:
                # chunked mode: piggyback the decode batch on the chunk —
                # one fused step advances both (bounded interference)
                batch = [b for b in self.backend.attached(w)
                         if getattr(b, "state", "") == "decoding"]
                if batch:
                    try:
                        dur, payload, toks = self.backend.run_fused_prefill(
                            w, task, s, batch)
                    except WorkerDiedError as e:
                        self._on_rpc_death(e, w, task, s)
                        return
                    w._running = True
                    w.util_busy_s += dur
                    s._rt_chain_worker = (w.kind, w.idx)
                    due = self.now + dur
                    self.events.at(
                        due,
                        lambda w=w, task=task, payload=payload, batch=batch,
                               toks=toks, due=due:
                            self._on_fused_done(w, task, payload, batch,
                                                toks, due),
                        "fused-step")
                    self._post_launch(w, task)
                    return
            extra = 0.0
            try:
                if w.kind == "prefill":
                    waited = self.now - task.enqueue_time
                    self._plan_cache(w, task, s)
                    extra = self.backend.history_read_extra(
                        w, task, d, waited, self._hist_to_read(w, task, s))
                dur, payload = self.backend.run_prefill(w, task, s, d)
            except WorkerDiedError as e:
                self._unpin_cache(w, task)
                self._on_rpc_death(e, w, task, s)
                return
            w._running = True
            w.util_busy_s += dur + extra
            s._rt_chain_worker = (w.kind, w.idx)
            due = self.now + (extra + dur)
            self.events.at(
                due,
                lambda w=w, task=task, payload=payload, due=due:
                    self._on_prefill_done(w, task, payload, due),
                "prefill-done")
            self._post_launch(w, task)
            return
        if w.kind == "decode":
            self._run_decode(w)
        elif self._try_steal(w):
            self._kick(w)               # run the stolen chunk immediately

    def _post_launch(self, w, task: PrefillTask) -> None:
        """Work just started on ``w``: expose it to the steal planner, and
        let a prefill worker whose queue fell below the watermark prefetch
        backlog from a more loaded peer before it next idles (watermark 0 =
        no prefetch; steal only on idle)."""
        w.launches += 1
        w.queue_wait_s += self.now - task.enqueue_time
        w._rt_running_task = task
        st = self.coordinator.stealing
        if (st is not None and w.kind == "prefill"
                and len(w.prefill_queue) < st.watermark):
            self._try_steal(w)

    # -- cross-worker work stealing (§12) -----------------------------------
    def _try_steal(self, w) -> bool:
        """Migrate the most profitable queued chunk from the most backlogged
        prefill worker onto ``w``.  Only net-positive moves happen — the
        Coordinator charges the KV-locality penalty before accepting."""
        if (self.coordinator.stealing is None or w.kind != "prefill"
                or not w.alive):
            return False
        with span("repro.steal_scan"):
            plan = self.coordinator.plan_steal(
                w, self.prefill_workers, self.now, self.sessions,
                self.decode_workers)
        if plan is None:
            return False
        victim, task = plan
        victim.prefill_queue.remove(task)
        s = self.sessions[task.session_id]
        self.backend.on_steal(task, s, victim, w)
        task.enqueue_time = self.now    # lazy-read prefetch restarts here
        task.routed_to = f"remote:{w.idx}"
        w.prefill_queue.append(task)
        return True

    # -- decode-local offload (DESIGN.md §14) --------------------------------
    def _try_offload(self, d) -> None:
        """Migrate queued local prefill chunks off a saturated decode
        worker onto prefill workers — the first placement revisit that
        crosses the prefill/decode phase boundary.  The Coordinator owns
        the policy (saturation trigger, hysteresis, budget, profit gate);
        this loop executes accepted moves one at a time, re-projecting the
        stall after each, until the plan disengages."""
        if self.coordinator.offload is None or not d.alive:
            return
        while True:
            batch = [b for b in self.backend.attached(d)
                     if getattr(b, "state", "") == "decoding"]
            with span("repro.offload_scan"):
                plan = self.coordinator.plan_offload(
                    d, self.prefill_workers, self.now, self.sessions, batch)
            if plan is None:
                return
            task, w = plan
            d.prefill_queue.remove(task)
            s = self.sessions[task.session_id]
            task.migrations += 1
            try:
                self.backend.on_migrate(task, s, d, w)
            except WorkerDiedError as e:
                # destination died mid-handoff (real SIGKILL under the proc
                # transport): the chunk re-enters the standard recovery
                # path — the failure handler re-routes it like an orphan
                self._on_rpc_death(e, w, task, s)
                continue
            task.enqueue_time = self.now    # lazy-read overlap restarts
            task.routed_to = f"remote:{w.idx}"
            w.prefill_queue.append(task)
            self._kick(w)

    def _steal_scan(self) -> None:
        """A queue just grew: give every drained prefill worker a chance to
        steal (an idle worker is not otherwise re-kicked by enqueues that
        land elsewhere)."""
        st = self.coordinator.stealing
        if st is None:
            return
        for w in self.prefill_workers:
            if (w.alive and not w._running
                    and len(w.prefill_queue) <= st.watermark):
                self._kick(w)           # drains queue, then tries stealing

    def _hist_to_read(self, w, task: PrefillTask, s) -> int:
        """History KV the worker must lazily pull before this chunk: none if
        the previous chunk of the same round just ran here (KV resident in
        the worker's working cache), else the full session history."""
        if task.incr_offset > 0 and s._rt_chain_worker == (w.kind, w.idx):
            return 0
        return task.l_hist

    # -- global KV pool hooks (DESIGN.md §17) --------------------------------
    @property
    def _pool(self):
        """The Coordinator-owned PoolManager, or None when pooling is off."""
        return self.coordinator.pool_mgr

    def _plan_cache(self, w, task: PrefillTask, s) -> None:
        """Chunk launch: resolve how much of the history this chunk must
        lazily read is already resident in ``w``'s page pool, pin that
        prefix for the chunk's duration, and surface the hit into the
        decision log — BEFORE the backend prices or performs the read
        (both see ``task.cache_plan``)."""
        pm = self._pool
        if pm is None or self._hist_to_read(w, task, s) <= 0:
            return
        plan = pm.plan_for((w.kind, w.idx), task.session_id, task.l_hist)
        task.cache_plan = plan
        if plan.prefix_tokens <= 0:
            return
        self.coordinator.note_cache("cache_hit", task, w.idx,
                                    plan.prefix_tokens)
        if plan.spilled_tokens > 0:
            self.coordinator.note_cache("promote", task, w.idx)
        pm.execute_plan((w.kind, w.idx), task.session_id, plan, task)

    def _unpin_cache(self, w, task: Optional[PrefillTask]) -> None:
        """Chunk execution ended (or died): release the plan's page pins."""
        pm = self._pool
        if pm is not None and task is not None and w.alive:
            pm.finish_chunk((w.kind, w.idx), task.cache_plan)

    # -- prefill completion, write-back, decode join (§3 step 3) ------------
    def _settle(self, w, due: float) -> None:
        """A step launched on ``w`` completed: count it, and how long its
        completion ran past ``due`` (launch time plus the duration the
        backend returned).  That is 0 on the logical loop by construction;
        on a wall-clock loop it is the time the finished step waited for
        the host thread."""
        w.steps += 1
        w.lag_s += self.now - due

    def _on_prefill_done(self, w, task: PrefillTask, payload,
                         due: float) -> None:
        self._settle(w, due)
        w._running = False
        w._rt_running_task = None
        w.tasks_done += 1
        s = self.sessions[task.session_id]
        self._unpin_cache(w, task)
        if task.gen != s._rt_gen:
            self._kick(w)
            return
        pm = self._pool
        if pm is not None and w.kind == "prefill" and w.alive:
            # the executing worker materially holds [0, l_hist + l_incr)
            # right now: key the span and pool its full pages (§17)
            end = task.l_hist + task.l_incr
            pm.extend_stream(
                task.session_id, end,
                lambda lo, n: self.backend.prefill_symbols(s, task, lo, n))
            pm.insert_range(("prefill", w.idx), task.session_id, 0, end,
                            task)
        d = self._bound_decode(s)
        if not d.alive:
            self._rebind(s, task)
            self._kick(w)
            return
        delay = self.backend.writeback_delay(w, task, d)
        self.events.after(
            delay, lambda: self._on_join(s, task, payload, w), "join")
        self._kick(w)

    def _on_join(self, s, task: PrefillTask, payload, stat_worker) -> None:
        if task.gen != s._rt_gen:
            return
        d = self._bound_decode(s)
        if not d.alive:
            self._rebind(s, task)
            return
        if not self.backend.can_join(d, s):
            # join backpressure: all decode slots busy (e.g. after a failure
            # halves capacity) — the KV increment is in hand, wait for a
            # resident session to finish
            self.events.after(
                self.admission_retry_s,
                lambda: self._on_join(s, task, payload, stat_worker),
                "join-retry")
            return
        s.context_len = task.l_hist + task.l_incr
        d.mem_tokens += task.l_incr
        try:
            self.backend.on_join(d, s, task, payload)
        except WorkerDiedError as e:
            d.mem_tokens -= task.l_incr     # the KV write-back never landed
            self._on_rpc_death(e, d, task, s)
            return
        pm = self._pool
        if pm is not None:
            end = task.l_hist + task.l_incr
            pm.extend_stream(
                task.session_id, end,
                lambda lo, n: self.backend.prefill_symbols(s, task, lo, n))
            if stat_worker.kind == "prefill":
                # remote join: the increment tree just crossed to the
                # decode worker — pool its pages there too (§17)
                pm.insert_range(("decode", d.idx), task.session_id,
                                task.l_hist, end, task)
        if not task.is_final_chunk:
            rest, s._rt_rest = s._rt_rest, None
            self._dispatch(s, rest)     # re-derives the next chunk size
            self._kick(d)       # decode interleaves while the chunk queues
            return
        ttft = self.now - task.arrival_time
        s.ttfts.append(ttft)
        stat_worker.ttft_stat.add(self.now, ttft)
        s.tokens_this_round = 0
        s.last_token_time = self.now
        s.state = "decoding"
        self._kick(d)

    # -- decode (§3 step 4) --------------------------------------------------
    def _run_decode(self, d) -> None:
        batch = [s for s in self.backend.attached(d)
                 if getattr(s, "state", "") == "decoding"]
        if not batch:
            return
        d._running = True
        try:
            dur, toks = self.backend.run_decode(d, batch)
        except WorkerDiedError as e:
            d._running = False
            self._on_rpc_death(e, d, None, None)
            return
        d.util_busy_s += dur
        due = self.now + dur
        self.events.at(
            due, lambda: self._on_step_end(d, batch, toks, due),
            "decode-step")

    def _on_step_end(self, d, batch: List, toks: Dict, due: float) -> None:
        self._settle(d, due)
        d._running = False
        if not d.alive:
            return
        for s in self._apply_decode_outcome(d, batch, toks):
            self._on_round_complete(s, d)
        self._kick(d)

    def _apply_decode_outcome(self, d, batch: List, toks: Dict) -> List:
        """Per-token accounting for one (possibly fused) decode step;
        returns sessions whose round just finished."""
        finished = []
        for s in batch:
            if s.state != "decoding" or s.decode_worker != d.idx:
                continue                     # detached / rebound mid-step
            itl = self.now - s.last_token_time
            s.itls.append(itl)
            d.itl_stat.add(self.now, itl)
            s.last_token_time = self.now
            s.tokens_this_round += 1
            s.context_len += 1
            d.mem_tokens += 1
            self.backend.on_token(d, s, toks.get(s.session_id))
            if s.tokens_this_round >= s.rounds[s.current_round].decode_len:
                finished.append(s)
        return finished

    def _on_fused_done(self, d, task: PrefillTask, payload, batch: List,
                       toks: Dict, due: float) -> None:
        """A fused chunk+decode step ended: settle the decode tokens, then
        land the chunk (local write-back is free)."""
        self._settle(d, due)
        d._running = False
        d._rt_running_task = None
        d.tasks_done += 1
        s = self.sessions[task.session_id]
        if not d.alive:
            if task.gen == s._rt_gen:
                self._rebind(s, task)
            return
        for b in self._apply_decode_outcome(d, batch, toks):
            self._on_round_complete(b, d)
        if task.gen == s._rt_gen and d.idx == s.decode_worker:
            self._on_join(s, task, payload, d)   # continues via _kick(d)
        else:
            self._kick(d)

    def _on_round_complete(self, s, d) -> None:
        pm = self._pool
        if pm is not None:
            # key the round's decode span so the next round's history pages
            # are addressable (the tokens live on the decode worker; no
            # material capture — only remote joins stage extract trees)
            r0 = s.current_round
            pm.extend_stream(
                s.session_id, s.context_len,
                lambda lo, n: self.backend.decode_symbols(s, r0, lo, n))
        r = s.rounds[s.current_round]
        s.current_round += 1
        if s.current_round >= s.num_rounds:
            s.finish_time = self.now
            s.state = "done"
            d.mem_tokens -= s.context_len
            self.backend.detach(d, s)
            if pm is not None:
                pm.release_session(s.session_id)
            return
        s.state = "env"
        gen = s._rt_gen
        self.events.after(
            r.env_delay,
            lambda: gen == s._rt_gen and self._on_env_done(s), "env-done")

    def _on_env_done(self, s) -> None:
        task = PrefillTask(
            session_id=s.session_id, round_idx=s.current_round,
            l_hist=s.context_len,
            l_incr=self.backend.incr_len(s, s.current_round),
            enqueue_time=self.now, arrival_time=self.now, gen=s._rt_gen,
            tenant=getattr(s, "tenant", "default"))
        self._dispatch(s, task)

    # -- failures / recovery (§6 / §13) -------------------------------------
    def _on_failure(self, kind: str, idx: int, inflight=None,
                    spawn_gen=None) -> None:
        """``inflight``: an optional (session, task) pair that was mid-RPC
        on the dying decode worker — it must be rebound WITH its task so
        the un-joined suffix of the round's increment is re-prefilled (the
        victim scan alone cannot know about it and would replay only the
        transcript).

        ``spawn_gen``: incarnation stamp captured by ``schedule_failure``.
        When set, the failure only applies to that incarnation — a
        replacement spawned under the same stable id (even at the same
        logical time) is spared."""
        w = self.worker_by_id(kind, idx)     # stable id, never list position
        if w is None or not w.alive:
            return
        if spawn_gen is not None and w._rt_spawn_gen != spawn_gen:
            return                           # same id, later incarnation
        w.alive = False
        # real failure injection under the proc transport: the worker
        # process is SIGKILL'd — no flush, no goodbye (DESIGN.md §13).
        kill = getattr(w, "kill", None)
        if kill is not None:
            kill()
        orphans = list(w.prefill_queue)
        w.prefill_queue.clear()
        if self._pool is not None:
            self._pool.drop_worker((kind, idx))   # its pages die with it
        if self.fleet is not None:
            # swap to the (fleet-1) lattice cell BEFORE rebinding victims:
            # a replacement spawned here absorbs the recovery traffic (and
            # keeps the last-decode-worker death from dropping everything)
            self.fleet.on_death(kind, idx, self.now)
        if kind == "decode":
            victims = list(self.backend.attached(w))
            self.backend.on_decode_failure(w)
            w.mem_tokens = 0
            handled = set()
            if inflight is not None:
                s, task = inflight
                if (task.gen == s._rt_gen
                        and s.state not in ("done", "dropped")):
                    self._rebind(s, task)
                    handled.add(s.session_id)
            for task in orphans:             # queued local prefills: the
                s = self.sessions[task.session_id]   # increment is re-prefilled
                if task.gen != s._rt_gen:
                    continue
                self._rebind(s, task)
                handled.add(s.session_id)
            for s in victims:
                if (s.session_id in handled
                        or s.state in ("done", "dropped")):
                    continue
                self._rebind(s, None)
        else:
            for task in orphans:             # re-route to surviving workers
                s = self.sessions[task.session_id]
                if task.gen != s._rt_gen:
                    continue
                self._dispatch(s, task)

    def _on_rpc_death(self, err: WorkerDiedError, w, task, s) -> None:
        """A backend call failed mid-flight because a worker process died
        under us (chaos SIGKILL outside the scheduled-failure path).

        ``w`` is the worker we were driving; the DEAD worker is named by
        ``err`` (it may instead be the bound decode worker contacted for a
        history read or KV write-back).  Route through the standard
        failure handler, handing it the in-flight task — already popped
        from its queue, so the orphan scan cannot see it; if the dead
        worker is the session's bound decode worker the handler rebinds
        WITH the task (the un-joined increment suffix re-prefills), else
        the chunk is re-routed here like an orphan."""
        w._running = False
        w._rt_running_task = None
        gen = s._rt_gen if s is not None else None
        inflight = None
        if (err.kind == "decode" and s is not None and task is not None
                and err.idx == s.decode_worker):
            inflight = (s, task)
        self._on_failure(err.kind, err.idx, inflight=inflight)
        if s is not None and s.state not in ("done", "dropped") \
                and task is not None and task.gen == gen == s._rt_gen:
            # session not superseded by the failure handler (its bound
            # decode worker survives): the executing prefill worker died —
            # re-route the chunk exactly like an orphan
            self._dispatch(s, task)
        if w.alive and not w._running:
            self._kick(w)               # continue the survivor's queue

    def _rebind(self, s, task: Optional[PrefillTask]) -> None:
        """Decode worker died: drop stale in-flight work, re-bind, and
        re-prefill the context (modeled) / replay the transcript (live) —
        minus any prefix the rebind target's page pool still holds
        (DESIGN.md §17): recovery routes through a CachePlan instead of
        blindly re-reading the full history."""
        if s.state in ("done", "dropped"):
            return
        if not any(d.alive for d in self.decode_workers):
            s.state = "dropped"
            return
        self.coordinator.rebinds += 1
        s._rt_gen += 1
        pending = self._pending_increment(s, task)
        s._rt_rest = None
        s._rt_chain_worker = None
        pm = self._pool
        if pm is not None:
            self._key_context(s, pending)
        d_new = self.coordinator.bind(s, self.decode_workers)
        rplan = None
        if pm is not None:
            rplan = pm.recovery_plan(("decode", d_new.idx), s.session_id,
                                     s.context_len + pending[2])
        rtask = self.backend.make_recovery_task(s, task, self.now, pending,
                                                d_new, rplan)
        rtask.gen = s._rt_gen
        rtask.tenant = getattr(s, "tenant", "default")
        resident = rtask.l_hist     # live may fall back to 0 (slot pressure)
        if pm is not None and resident > 0:
            # the rebind target already held a prefix of the dead context:
            # the replay starts there (live attach happened inside
            # make_recovery_task); account the residency like any hit
            d_new.mem_tokens += resident
            pm.execute_plan(("decode", d_new.idx), s.session_id, rplan,
                            rtask)
            pm.finish_chunk(("decode", d_new.idx), rplan)
            self.coordinator.note_cache("cache_hit", rtask, d_new.idx,
                                        resident)
            if rplan.spilled_tokens > 0:
                self.coordinator.note_cache("promote", rtask, d_new.idx)
        self._dispatch(s, rtask)

    def _key_context(self, s, pending) -> None:
        """Before a recovery replay: extend the symbol stream over the whole
        context the replay will rebuild — the partially-decoded span of the
        current round plus the never-joined increment suffix.  Streams are
        append-only, so the replay can NEVER re-key positions the stream
        already addressed — which is what keeps a rebuilt prefix hashing
        identically to the pages it dedups against."""
        pm = self._pool
        if s.state == "decoding" and s.tokens_this_round > 0:
            r0 = s.current_round
            pm.extend_stream(
                s.session_id, s.context_len,
                lambda lo, n: self.backend.decode_symbols(s, r0, lo, n))
        r, off, pend = pending
        if pend > 0:
            synth = PrefillTask(
                session_id=s.session_id, round_idx=r, l_hist=s.context_len,
                l_incr=pend, enqueue_time=0.0, arrival_time=0.0,
                incr_offset=off)
            pm.extend_stream(
                s.session_id, s.context_len + pend,
                lambda lo, n: self.backend.prefill_symbols(s, synth, lo, n))

    def _pending_increment(self, s, task: Optional[PrefillTask]):
        """The un-joined suffix of the current round's increment, which the
        recovery prefill must cover on top of the (lost) context:
        (round_idx, offset_into_increment, token_count).  A failed task plus
        its parked remainder; or, for a session waiting out an env delay,
        the whole upcoming increment (its round was never dispatched)."""
        if task is not None:
            rest = getattr(s, "_rt_rest", None)
            pend = task.l_incr + (rest.l_incr if rest is not None else 0)
            return (task.round_idx, task.incr_offset, pend)
        r = min(s.current_round, s.num_rounds - 1)
        if s.state == "env":
            return (r, 0, self.backend.incr_len(s, r))
        return (r, 0, 0)                 # round fully joined (decoding)
