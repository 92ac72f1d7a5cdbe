"""Execution backends: how the unified runtime obtains durations & tokens
(DESIGN.md §2).

The :class:`ServingRuntime` owns the multi-round protocol state machine; an
:class:`ExecutionBackend` answers the only questions that differ between the
planner's estimator and a real deployment:

  * how long does this prefill / decode step / KV transfer take?
  * what tokens did it produce, and what KV needs to move?

``ModeledBackend`` answers from the fitted :class:`PerfModel` (discrete-event
simulation — paper App. A.1); ``LiveBackend`` answers by *running* the JAX
engines and timing them (the CPU-scale twin of a TPU deployment).  Everything
else — binding, routing, queue ordering, chunking, failures, rebinding,
SLO accounting — is shared code in the protocol engine.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from repro.core.perf_model import PerfModel
from repro.core.types import PrefillTask
from repro.runtime.tracing import span

#: payload of a completed prefill: (placement, kv_increment, first_token)
PrefillPayload = Tuple[str, Optional[Dict], Optional[int]]


class WorkerDiedError(RuntimeError):
    """A live RPC to a worker process failed because the process is gone
    (SIGKILL'd, crashed, or hung past the deadline) — raised by the proc
    transport (``repro.serving.rpc``) and converted by the ServingRuntime
    into the standard worker-failure path (DESIGN.md §13).  ``kind``/``idx``
    identify the dead worker by its stable id."""

    def __init__(self, kind: str, idx: int, msg: str = ""):
        super().__init__(f"{kind} worker {idx} died: {msg}")
        self.kind = kind
        self.idx = idx


class ExecutionBackend:
    """Duck-typed interface; both implementations below are the spec."""

    # -- sessions ----------------------------------------------------------
    def incr_len(self, session, round_idx: int) -> int:
        raise NotImplementedError

    # -- admission ---------------------------------------------------------
    def admit_local(self, decode_worker, session) -> bool:
        """Reserve local execution resources (a batch slot, for live
        continuous batching).  False -> the runtime retries shortly
        (admission backpressure)."""
        return True

    # -- KV-pool content addressing (DESIGN.md §17) ------------------------
    def prefill_symbols(self, session, task: PrefillTask, lo: int,
                        n: int) -> List:
        """Content symbols for absolute context positions [lo, lo+n) inside
        ``task``'s chunk span — what the PoolManager hashes into page keys.
        The live backend returns real token ids (identical prompts dedup
        across sessions); the modeled backend returns synthetic symbols
        that encode the session's declared sharing structure."""
        raise NotImplementedError

    def decode_symbols(self, session, round_idx: int, lo: int,
                       n: int) -> List:
        """Content symbols for absolute positions [lo, lo+n) inside the
        round's just-generated decode span (context_len is final)."""
        raise NotImplementedError

    # -- prefill -----------------------------------------------------------
    def history_read_extra(self, worker, task: PrefillTask, decode_worker,
                           waited: float, hist_len: int) -> float:
        """Residual lazy-read stall before a remote prefill can start:
        the history KV pull not already hidden under queue wait (§6)."""
        return 0.0

    def run_prefill(self, worker, task: PrefillTask, session,
                    decode_worker) -> Tuple[float, Optional[PrefillPayload]]:
        """Execute (or predict) one prefill chunk; returns (seconds, payload)."""
        raise NotImplementedError

    def writeback_delay(self, worker, task: PrefillTask,
                        decode_worker) -> float:
        """Incremental KV write-back latency between prefill completion and
        the session joining its decode batch (§3 step 3.ii)."""
        return 0.0

    def can_join(self, decode_worker, session) -> bool:
        """Admission gate for a remotely-prefilled session landing on the
        decode worker (a batch slot must exist).  False -> the runtime
        retries the join shortly; the KV increment is already in hand."""
        return True

    def on_join(self, decode_worker, session, task: PrefillTask,
                payload: Optional[PrefillPayload]) -> None:
        """Apply side effects of a chunk landing on the decode worker
        (cache insertion, transcript bookkeeping, batch membership)."""

    # -- decode ------------------------------------------------------------
    def attached(self, decode_worker) -> List:
        """Sessions whose KV is resident on this decode worker."""
        raise NotImplementedError

    def run_decode(self, decode_worker,
                   batch: List) -> Tuple[float, Dict[int, Optional[int]]]:
        """One continuous-batching step over ``batch``; returns
        (seconds, {session_id: next_token_or_None})."""
        raise NotImplementedError

    def run_fused_prefill(self, decode_worker, task: PrefillTask, session,
                          batch: List):
        """Chunked-mode local prefill piggybacking the decode batch: one
        step that prefills the chunk AND advances every decoding session by
        one token (weight reads amortize — the chunk bounds the marginal
        decode delay).  Returns (seconds, payload, {session_id: token})."""
        raise NotImplementedError

    def on_token(self, decode_worker, session, token: Optional[int]) -> None:
        """Per-token side effects beyond the runtime's generic accounting."""

    def detach(self, decode_worker, session) -> None:
        """Release the session's residency (slot / membership)."""
        raise NotImplementedError

    def on_decode_failure(self, decode_worker) -> None:
        """Tear down all residency on a failed decode worker."""
        for s in list(self.attached(decode_worker)):
            self.detach(decode_worker, s)

    # -- work stealing (DESIGN.md §12) -------------------------------------
    def on_steal(self, task: PrefillTask, session, src_worker,
                 dst_worker) -> None:
        """A queued chunk migrates from ``src_worker`` to ``dst_worker``.

        Base semantics (both backends): chunk-chain locality does not
        migrate — if the session's previous chunk ran on the source, the
        thief must lazily re-read the full history from the bound decode
        worker (the KV-locality penalty the Coordinator charged when it
        accepted the steal)."""
        if getattr(session, "_rt_chain_worker", None) == (
                src_worker.kind, src_worker.idx):
            session._rt_chain_worker = None

    # -- decode-local offload (DESIGN.md §14) ------------------------------
    def on_migrate(self, task: PrefillTask, session, src_decode,
                   dst_prefill) -> None:
        """A queued LOCAL chunk migrates off a saturated decode worker onto
        ``dst_prefill`` — the placement revisit that crosses the
        prefill/decode phase boundary.

        Base semantics (both backends): as with stealing, chunk-chain
        locality does not migrate — if the session's previous chunk ran
        locally on ``src_decode``, the destination must lazily re-read the
        full history (the KV-locality penalty ``plan_offload`` charged),
        and the increment now pays a real write-back on completion.  May
        raise :class:`WorkerDiedError` when the destination process died
        mid-handoff (proc transport); the runtime converts that into the
        standard recovery path."""
        if getattr(session, "_rt_chain_worker", None) == (
                src_decode.kind, src_decode.idx):
            session._rt_chain_worker = None

    # -- fault tolerance ---------------------------------------------------
    def make_recovery_task(self, session, task: Optional[PrefillTask],
                           now: float, pending, decode_worker=None,
                           plan=None) -> PrefillTask:
        """Reset the session after its decode worker died and build the
        re-prefill task that reconstructs its context PLUS the un-joined
        suffix of the current round's increment.  ``pending`` is
        (round_idx, offset_into_increment, token_count) as computed by the
        runtime — covering a mid-prefill task with its queued sibling
        chunks, or a never-dispatched round during an env delay.

        ``decode_worker``/``plan`` (DESIGN.md §17): the rebind target and
        its recovery CachePlan — when the target's pool already holds a
        prefix of the dead context, the replay starts at
        ``plan.prefix_tokens`` of resident history instead of re-prefilling
        from zero (plan=None keeps the full-replay behaviour)."""
        raise NotImplementedError


class ModeledBackend(ExecutionBackend):
    """Durations predicted by the alpha-beta :class:`PerfModel` (§3)."""

    def __init__(self, perf: PerfModel, *, kv_overlap: bool = True):
        self.perf = perf
        self.kv_overlap = kv_overlap

    def incr_len(self, session, round_idx: int) -> int:
        return session.rounds[round_idx].prefill_len

    def prefill_symbols(self, session, task, lo, n) -> List:
        # synthetic content: round-0 positions inside a declared shared
        # prefix group hash identically across the group's sessions; all
        # other positions are session-unique
        r = task.round_idx
        roff = task.incr_offset + (lo - task.l_hist)
        grp = getattr(session, "prefix_group", None)
        out = []
        for j in range(roff, roff + n):
            if r == 0 and grp is not None and j < grp[1]:
                out.append(("g", grp[0], j))
            else:
                out.append(("s", session.session_id, r, j))
        return out

    def decode_symbols(self, session, round_idx, lo, n) -> List:
        # tokens_this_round (not the round's decode_len) so a mid-round
        # rebind keys the PARTIAL decoded span with correct offsets
        start = session.context_len - session.tokens_this_round
        return [("d", session.session_id, round_idx, lo - start + j)
                for j in range(n)]

    def history_read_extra(self, worker, task, decode_worker, waited,
                           hist_len) -> float:
        if hist_len <= 0:
            return 0.0
        plan = task.cache_plan
        if plan is None:
            t_read = self.perf.t_kv_between(hist_len, decode_worker, worker)
        else:
            # resident pages are free, host-tier pages pay the promote DMA,
            # only the miss suffix crosses the link (DESIGN.md §17)
            t_read = (self.perf.t_kv_between(plan.miss_tokens, decode_worker,
                                             worker)
                      if plan.miss_tokens > 0 else 0.0)
            t_read += self.perf.t_promote(plan.spilled_tokens)
        if self.kv_overlap:
            return max(0.0, t_read - waited)   # lazy read overlap (§6)
        return t_read

    def run_prefill(self, worker, task, session, decode_worker):
        dur = self.perf.t_pre(task.l_hist, task.l_incr, worker.tp,
                              worker.speed)
        return dur, None

    def writeback_delay(self, worker, task, decode_worker) -> float:
        if worker.kind == "prefill":
            return self.perf.t_kv_between(task.l_incr, worker, decode_worker)
        return 0.0

    def on_join(self, decode_worker, session, task, payload) -> None:
        if session not in decode_worker.sessions:
            decode_worker.sessions.append(session)

    def attached(self, decode_worker) -> List:
        return decode_worker.sessions

    def run_decode(self, decode_worker, batch):
        avg_ctx = sum(s.context_len for s in batch) / len(batch)
        dt = self.perf.t_dec(len(batch), decode_worker.tp, avg_ctx,
                             decode_worker.speed)
        return dt, {s.session_id: None for s in batch}

    def run_fused_prefill(self, decode_worker, task, session, batch):
        # T_fused (§3/DESIGN.md §11): chunk prefill + marginal decode under
        # one dispatch — the same cost family the planner and tuner invert
        avg_ctx = sum(s.context_len for s in batch) / len(batch)
        dur = self.perf.t_fused(task.l_hist, task.l_incr, len(batch),
                                decode_worker.tp, avg_ctx,
                                decode_worker.speed)
        return dur, None, {s.session_id: None for s in batch}

    def detach(self, decode_worker, session) -> None:
        if session in decode_worker.sessions:
            decode_worker.sessions.remove(session)

    def make_recovery_task(self, session, task, now: float, pending,
                           decode_worker=None, plan=None) -> PrefillTask:
        """Re-prefill the dead context — minus whatever prefix the rebind
        target's pool still holds (DESIGN.md §17 recovery fix)."""
        round_idx, _, pend = pending
        total = session.context_len + pend
        resident = plan.prefix_tokens if plan is not None else 0
        session.context_len = resident
        return PrefillTask(
            session_id=session.session_id, round_idx=round_idx,
            l_hist=resident, l_incr=max(total - resident, 1),
            incr_offset=resident, enqueue_time=now,
            arrival_time=task.arrival_time if task else now,
            is_initial=False)


class LiveBackend(ExecutionBackend):
    """Durations measured from real JAX engine calls (repro.serving)."""

    def __init__(self, perf: PerfModel, *, model_kv_time: bool = False):
        self.perf = perf
        self.model_kv_time = model_kv_time
        self.kv_steal_bytes = 0     # history payload re-read after steals
        self.kv_migrate_bytes = 0   # history re-read after decode offload
        #: material page store (serving.kv_pool.MaterialStore) when the
        #: global KV pool is on — set by the cluster wiring (DESIGN.md §17)
        self.kv_store = None

    def incr_len(self, session, round_idx: int) -> int:
        return len(session.prompt_tokens[round_idx])

    def prefill_symbols(self, session, task, lo, n) -> List:
        # real token ids: identical prompt prefixes hash to identical page
        # chains, so dedup is cross-session by construction
        r = task.round_idx
        roff = task.incr_offset + (lo - task.l_hist)
        return [int(t) for t in session.prompt_tokens[r][roff:roff + n]]

    def decode_symbols(self, session, round_idx, lo, n) -> List:
        # the transcript holds the full context token-for-token, so
        # absolute positions index it directly
        return [int(t) for t in session.transcript[lo:lo + n]]

    def on_steal(self, task, session, src_worker, dst_worker) -> None:
        super().on_steal(task, session, src_worker, dst_worker)
        # workers own the handoff accounting so the proc transport can run
        # it inside the thief's process (same engine-adjacent code path)
        self.kv_steal_bytes += dst_worker.steal_handoff(task, session)

    def on_migrate(self, task, session, src_decode, dst_prefill) -> None:
        super().on_migrate(task, session, src_decode, dst_prefill)
        # runs in the destination process under the proc transport; a
        # WorkerDiedError here (destination SIGKILL'd mid-handoff)
        # propagates so the runtime re-routes the chunk — unlike steals,
        # the source queue entry is already gone at this point
        self.kv_migrate_bytes += dst_prefill.migrate_handoff(task, session)

    def admit_local(self, decode_worker, session) -> bool:
        if session.slot is None:
            if decode_worker.free_slot() is None:
                return False
            decode_worker.allocate(session)
        return True

    def can_join(self, decode_worker, session) -> bool:
        return (session.slot is not None
                or decode_worker.free_slot() is not None)

    def _read_history(self, worker, task, session, decode_worker):
        """The lazy history pull, pool-spliced when a CachePlan says part
        of it is already resident on ``worker`` (DESIGN.md §17): assemble
        the resident prefix from the material store and pull only the miss
        suffix off the decode worker — the splice is what makes the hit
        bytes *measured* savings, not a modeling assumption."""
        plan = task.cache_plan
        if (self.kv_store is not None and plan is not None
                and plan.prefix_tokens > 0):
            from repro.serving.kv_transfer import concat_extracts
            prefix = self.kv_store.assemble(("prefill", worker.idx), plan)
            if prefix is not None:
                if plan.miss_tokens > 0:
                    suffix = decode_worker.history_extract_range(
                        session, plan.prefix_tokens, task.l_hist)
                    return concat_extracts([prefix, suffix], task.l_hist)
                return concat_extracts([prefix], task.l_hist)
        return decode_worker.history_extract(session)

    def run_prefill(self, worker, task, session, decode_worker):
        if worker.kind == "prefill":
            with span("repro.prefill", session=task.session_id,
                      round=task.round_idx):
                return self._run_remote(worker, task, session, decode_worker)
        dt, first = worker.local_prefill(task, session)
        return dt / worker.speed, ("local", None, first)

    def _run_remote(self, worker, task, session, decode_worker):
        import numpy as np
        from repro.serving.workers import kv_op, timed
        hist = None
        if task.l_hist > 0 and session.slot is not None:
            hist = kv_op(worker, "repro.kv.read_history", self._read_history,
                         worker, task, session, decode_worker)
        dt, out = timed(worker.execute, task, session, history_extract=hist)
        dt /= worker.speed
        if self.model_kv_time:
            dt += (self.perf.t_kv_between(task.l_hist, decode_worker, worker)
                   + self.perf.t_kv_between(task.l_incr, worker,
                                            decode_worker))
        if self.kv_store is not None:
            # the chunk's history + increment are in hand right here: stage
            # them so completion-time page capture can slice any span of
            # [0, l_hist + l_incr)
            parts = []
            if hist is not None:
                parts.append((0, task.l_hist, hist))
            parts.append((task.l_hist, task.l_hist + task.l_incr,
                          out["increment"]))
            self.kv_store.stage(("prefill", worker.idx), parts)
        return dt, ("remote", out["increment"], int(np.argmax(out["logits"])))

    def on_join(self, decode_worker, session, task, payload) -> None:
        placement, increment, first = payload
        with span("repro.join", session=task.session_id,
                  round=task.round_idx):
            if placement == "remote":
                decode_worker.attach(session, increment, task.l_hist, first,
                                     task.l_incr)
                if self.kv_store is not None:
                    # the increment tree is in hand at the join: stage it so
                    # the decode-side page capture can slice [l_hist, +l_incr)
                    self.kv_store.stage(
                        ("decode", decode_worker.idx),
                        [(task.l_hist, task.l_hist + task.l_incr, increment)])
            else:
                session.last_token = first
            toks = session.prompt_tokens[task.round_idx][
                task.incr_offset:task.incr_offset + task.l_incr]
            session.transcript.extend(int(t) for t in toks)

    def attached(self, decode_worker) -> List:
        return [s for s in decode_worker.slots if s is not None]

    def run_decode(self, decode_worker, batch):
        # mask slots whose session is not actively decoding (env wait,
        # prefill in flight) so the engine step skips them — XLA static
        # shapes decode a -1 token for empty rows
        keep = {s.session_id for s in batch}
        saved = {}
        for i, s in enumerate(decode_worker.slots):
            if s is not None and s.session_id not in keep:
                saved[i] = s
                decode_worker.slots[i] = None
        dt, toks = decode_worker.decode_once()
        for i, s in saved.items():
            decode_worker.slots[i] = s
        dt /= decode_worker.speed
        out = {}
        for slot, tok in toks.items():
            s = decode_worker.slots[slot]
            if s is not None:
                out[s.session_id] = tok
        return dt, out

    def run_fused_prefill(self, decode_worker, task, session, batch):
        dt, first, toks = decode_worker.fused_step(task, session, batch)
        return dt / decode_worker.speed, ("local", None, first), toks

    def on_token(self, decode_worker, session, token) -> None:
        session.last_token = token
        session.generated.append(token)
        session.transcript.append(token)

    def detach(self, decode_worker, session) -> None:
        decode_worker.detach(session)

    def make_recovery_task(self, session, task, now: float, pending,
                           decode_worker=None, plan=None) -> PrefillTask:
        """Replay the transcript as a fresh prefill (the KV is gone), then
        the un-prefilled remainder of the current round's increment — the
        transcript only holds tokens whose chunks had already joined.

        When the rebind target's pool holds a prefix of the dead context
        (``plan``, DESIGN.md §17), the material pages attach directly to
        the new decode worker and the replay starts from there."""
        import numpy as np
        session.slot = None
        r, off, pend = pending
        tail = session.prompt_tokens[r][off:off + pend]
        replay = np.concatenate([
            np.asarray(session.transcript, np.int32),
            np.asarray(tail, np.int32)])
        if len(replay) == 0:
            replay = session.prompt_tokens[0]
        session.prompt_tokens = list(session.prompt_tokens)
        session.prompt_tokens[r] = replay
        resident = 0
        if (plan is not None and plan.prefix_tokens > 0
                and plan.prefix_tokens < len(replay)
                and self.kv_store is not None and decode_worker is not None
                and decode_worker.free_slot() is not None):
            prefix = self.kv_store.assemble(
                ("decode", decode_worker.idx), plan)
            if prefix is not None:
                resident = plan.prefix_tokens
                decode_worker.attach(session, prefix, 0,
                                     int(replay[resident - 1]), resident)
        session.context_len = resident
        session.transcript = [int(t) for t in replay[:resident]]
        return PrefillTask(
            session_id=session.session_id, round_idx=r, l_hist=resident,
            l_incr=len(replay) - resident, incr_offset=resident,
            enqueue_time=now, arrival_time=now, is_initial=False)
