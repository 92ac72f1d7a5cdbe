"""Live cluster driver — live-backend facade over the unified runtime.

The multi-round protocol (binding, adaptive routing, lazy history reads,
incremental KV write-back, local prefill interference, chunked incremental
prefill, worker failure + session recovery by transcript replay, stragglers
and elastic scaling) runs in ``repro.runtime.ServingRuntime`` — the same
engine as the discrete-event simulator — with a :class:`LiveBackend` whose
every duration is *measured* from the actual engine call rather than
predicted: the CPU-scale twin of a TPU deployment.

Three transports (DESIGN.md §13/§16) behind one contract:

  * ``transport="inproc"`` (default): workers execute logically in parallel
    inside this process, placed one per local device in turn (``devices``)
    — KV moves as device copies, chip to chip on a multi-chip host.  This
    is the transport of a chip host: a chip belongs to one process.
  * ``transport="proc"``: every worker is a real OS process owning its own
    JAX engine (CPU and multi-host setups; refused on a TPU parent, which
    already holds the chip); KV bytes move over AF_UNIX RPC sockets
    (:class:`~repro.serving.kv_transfer.TransportKVPath` measures them) and
    ``fail_worker`` delivers a real ``SIGKILL``.
  * ``transport="tcp"``: the same worker processes over TCP stream sockets,
    so children can live on other machines (``TransportConfig.advertise``);
    the coordinator prices each link by its measured class
    (:class:`~repro.core.perf_model.LinkTopology`).

Decision logs and token accounting must match ``inproc`` on the same seeded
trace for every transport — the parity contract held by
``tests/test_multiproc_cluster.py``.

Configuration is three grouped objects (:class:`ClusterSpec`,
:class:`TransportConfig`, :class:`SchedPolicy` — ``repro.serving.config``);
the old ~25 flat kwargs keep working through a deprecation shim.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.perf_model import LinkTopology, PerfModel
from repro.core.routing import RoutingConfig
from repro.core.types import RoundSpec, SLOSpec
from repro.runtime import (
    ChunkTuner,
    Coordinator,
    KVPoolConfig,
    LiveBackend,
    OffloadConfig,
    PoolManager,
    ServingRuntime,
    StealingConfig,
    class_attainment,
    mean,
    p95,
)
from repro.serving.kv_pool import MaterialStore, supports_kv_pool
from repro.serving.config import (
    TRANSPORT_REGISTRY,
    ClusterSpec,
    SchedPolicy,
    TransportConfig,
    resolve_transport,
)
from repro.serving.engine import Engine, profile_engine
from repro.serving.workers import (
    LiveDecodeWorker,
    LivePrefillWorker,
    LiveSession,
)

# legacy flat kwargs -> which config object each one folds into
_LEGACY_SPEC = ("n_prefill", "n_decode", "tp", "max_slots", "max_len")
_LEGACY_POLICY = (
    "scheduler", "chunk_tokens", "adaptive_chunk", "chunk_headroom",
    "decode_chunk_tokens", "work_stealing", "steal_watermark",
    "steal_min_profit_s", "preemption", "decode_offload", "offload_guard",
    "offload_hysteresis", "offload_budget", "offload_min_profit_s", "packed")
_LEGACY_TRANSPORT = ("rpc_timeout_s",)


@dataclass
class LiveResult:
    sessions: List[LiveSession]
    slo_attainment: float
    avg_ttft: float
    avg_itl: float
    p95_ttft: float
    p95_itl: float
    local_fraction: float
    rebinds: int
    kv_bytes_moved: int
    logical_time: float
    wall_time: float
    steals: int = 0               # §12 counters (0 when stealing disabled)
    preempts: int = 0
    migrations: int = 0           # §14 counter (0 when offload disabled)
    kv_steal_bytes: int = 0       # history re-read payload from steals
    kv_migrate_bytes: int = 0     # history re-read payload from offloads
    transport: str = "inproc"     # §13: which execution transport ran
    kv_transfer_bytes: int = 0    # measured bytes over the RPC KV path
    kv_transfer_ms: float = 0.0   # measured wall time of those transfers
    kv_transfers: int = 0
    packed: bool = False          # §15: ragged packed fused path active
    fused_steps: int = 0          # fused chunk+decode steps executed
    fused_ms: float = 0.0         # total wall time of those steps
    tokens_uploaded: int = 0      # host->device token elements (inproc only)
    kv_pool: bool = False         # §17: global KV pool active this run
    cache_hits: int = 0           # §17 counters (0 when kv_pool disabled)
    cache_hit_tokens: int = 0
    kv_spills: int = 0
    kv_promotes: int = 0
    kv_hit_bytes: int = 0         # MEASURED bytes served from pooled pages
    kv_spill_bytes: int = 0       # measured hbm->host demotion bytes
    kv_promote_bytes: int = 0     # measured host->hbm read-back bytes
    replans: int = 0              # §18 counters (0 when autoscale disabled)
    role_swaps: int = 0
    #: tenant -> SLO attainment fraction (§19); {"default": ...} when the
    #: trace carries no tenant labels
    class_attainment: Dict[str, float] = dataclasses.field(
        default_factory=dict)


def _shim_legacy_kwargs(spec, transport, policy, legacy):
    """Normalize the config objects and fold pre-§16 flat kwargs into them.

    A bare kind string for ``transport`` is supported shorthand (no
    warning); any flat kwarg (``n_prefill=...``, ``chunk_tokens=...``,
    ``rpc_timeout_s=...``) warns ``DeprecationWarning`` and maps onto the
    matching config object.  Unknown kwargs raise ``TypeError`` exactly as
    a real signature would."""
    unknown = [k for k in legacy
               if k not in _LEGACY_SPEC + _LEGACY_POLICY + _LEGACY_TRANSPORT]
    if unknown:
        raise TypeError(
            f"LiveCluster() got unexpected keyword arguments {unknown}")
    if legacy:
        warnings.warn(
            "flat LiveCluster kwargs are deprecated; pass "
            "spec=ClusterSpec(...), transport=TransportConfig(...), "
            "policy=SchedPolicy(...) instead "
            f"(got {sorted(legacy)})",
            DeprecationWarning, stacklevel=3)
    spec = spec or ClusterSpec()
    policy = policy or SchedPolicy()
    tcfg = resolve_transport(transport)
    spec_kw = {k: legacy[k] for k in _LEGACY_SPEC if k in legacy}
    if spec_kw:
        spec = spec.replace(**spec_kw)
    pol_kw = {k: legacy[k] for k in _LEGACY_POLICY if k in legacy}
    if "decode_chunk_tokens" in pol_kw:     # SchedPolicy is tuple-typed
        pol_kw["decode_chunk_tokens"] = tuple(pol_kw["decode_chunk_tokens"])
    if pol_kw:
        policy = policy.replace(**pol_kw)
    if "rpc_timeout_s" in legacy:
        tcfg = tcfg.replace(rpc_timeout_s=legacy["rpc_timeout_s"])
    return spec, tcfg, policy


class LiveCluster:
    """Live serving cluster.

    New-style construction (DESIGN.md §16)::

        LiveCluster(cfg, spec=ClusterSpec(n_prefill=2, tp=2),
                    transport=TransportConfig(kind="tcp"),
                    policy=SchedPolicy(work_stealing=True))

    ``transport`` also accepts a bare kind string (``"inproc"``, ``"proc"``,
    ``"tcp"``) as shorthand.  The pre-§16 flat keyword arguments
    (``n_prefill=...``, ``chunk_tokens=...``, ...) keep working through a
    deprecation shim that warns and folds them into these objects.
    """

    def __init__(self, cfg: ModelConfig, *, spec: Optional[ClusterSpec] = None,
                 transport=None, policy: Optional[SchedPolicy] = None,
                 slo: Optional[SLOSpec] = None, seed: int = 0,
                 model_kv_time: bool = False, profile: bool = True,
                 lattice=None, devices: Optional[Sequence] = None,
                 **legacy):
        spec, tcfg, policy = _shim_legacy_kwargs(spec, transport, policy,
                                                 legacy)
        entry = TRANSPORT_REGISTRY[tcfg.kind]
        self.cfg = cfg
        self.spec = spec
        self.transport = tcfg.kind
        self.transport_config = tcfg
        self.policy = policy
        self.slo = slo or SLOSpec(ttft_thres=2.0, itl_thres=0.2)
        self._seed = seed
        self._max_len = spec.max_len
        self._max_slots = spec.max_slots
        self._pool = None
        self.kv_path = None

        self.prefill_workers: List = []
        self.decode_workers: List = []
        #: devices in-process tp=1 workers are placed on, one worker per
        #: device in turn (prefill workers first); tp>1 workers own a mesh
        self.devices = (list(devices or jax.local_devices())
                        if spec.tp == 1 else [None])
        if entry.multiprocess:
            from repro.serving.kv_transfer import TransportKVPath
            from repro.serving.worker_proc import ProcWorkerPool
            self.kv_path = TransportKVPath(default_class=entry.link_class)
            self._pool = ProcWorkerPool(
                cfg, max_len=spec.max_len, max_slots=spec.max_slots,
                seed=seed, kv_path=self.kv_path, packed=policy.packed,
                transport=tcfg, tp=spec.tp)
            specs = [("prefill", i, 0) for i in range(spec.n_prefill)]
            specs += [("decode", i,
                       policy.decode_chunk_tokens[i]
                       if i < len(policy.decode_chunk_tokens) else 0)
                      for i in range(spec.n_decode)]
            workers = self._pool.spawn_many(specs)
            self.prefill_workers = workers[:spec.n_prefill]
            self.decode_workers = workers[spec.n_prefill:]
            for i, w in enumerate(self.prefill_workers):
                if i < len(policy.prefill_classes):
                    w.pclass = policy.prefill_classes[i]   # dedicated (§19)
        else:
            key = jax.random.PRNGKey(seed)
            shared_engine_params = None
            for i in range(spec.n_prefill):
                eng = Engine(cfg, max_len=spec.max_len, key=key,
                             params=shared_engine_params, tp=spec.tp,
                             device=self._device_for("prefill", i))
                shared_engine_params = eng.params
                w = LivePrefillWorker(i, eng, tp=spec.tp)
                if i < len(policy.prefill_classes):
                    w.pclass = policy.prefill_classes[i]   # dedicated (§19)
                self.prefill_workers.append(w)
            for i in range(spec.n_decode):
                eng = Engine(cfg, max_len=spec.max_len, key=key,
                             params=shared_engine_params, tp=spec.tp,
                             device=self._device_for("decode", i))
                shared_engine_params = eng.params
                # planner-chosen per-worker chunk size (Deployment.decode_chunks())
                per_worker = (policy.decode_chunk_tokens[i]
                              if i < len(policy.decode_chunk_tokens) else 0)
                self.decode_workers.append(
                    LiveDecodeWorker(i, eng, max_slots=spec.max_slots,
                                     tp=spec.tp, chunk_tokens=per_worker,
                                     packed=policy.packed))

        self.perf = PerfModel(cfg)
        self.perf.topology = self._link_topology()
        if profile:
            # multiprocess transports: profile a coordinator-side probe
            # engine — identical params/config as the children
            # (deterministic init from the shared seed), so the fitted
            # coefficients transfer
            probe = self._probe_engine()
            profile_engine(probe, self.perf, tp=spec.tp,
                           prefill_lens=(16, 32, 64), hist_lens=(0, 32),
                           batches=(1, max(2, spec.max_slots // 2)),
                           fused=policy.adaptive_chunk,
                           # fit T_fused on the step the workers will run,
                           # so tuner/planner/offload inherit the speedup
                           packed=(policy.packed is not False))
        tuner = None
        if policy.adaptive_chunk:
            # online per-worker chunk sizing from the PROFILED perf model
            # (fused coefficients re-derive from the measured fits above)
            tuner = ChunkTuner(self.perf, itl_slo=self.slo.itl_thres,
                               headroom=policy.chunk_headroom)
        stealing = (StealingConfig(watermark=policy.steal_watermark,
                                   min_profit_s=policy.steal_min_profit_s,
                                   preemption=policy.preemption)
                    if policy.work_stealing else None)
        offload = (OffloadConfig(guard=policy.offload_guard,
                                 hysteresis=policy.offload_hysteresis,
                                 budget=policy.offload_budget,
                                 min_profit_s=policy.offload_min_profit_s)
                   if policy.decode_offload else None)
        # global KV pool (DESIGN.md §17): content-addressed page bookkeeping
        # + the material page store, gated on the arch supporting exact
        # page splicing (pure full-attention stacks only)
        pool_mgr = None
        self.kv_store = None
        if policy.kv_pool and supports_kv_pool(cfg):
            pool_mgr = PoolManager(
                KVPoolConfig(page_tokens=policy.kv_page_tokens,
                             hbm_pages=policy.kv_hbm_pages,
                             host_pages=policy.kv_host_pages),
                model_tag=getattr(cfg, "name", "model"))
            self.kv_store = MaterialStore()
            pool_mgr.listener = self.kv_store
        self.coordinator = Coordinator(
            perf=self.perf,
            routing=RoutingConfig.from_slo(self.slo),
            scheduler=policy.scheduler, seed=seed, chunk_tuner=tuner,
            stealing=stealing, offload=offload, pool_mgr=pool_mgr,
            cache_aware=policy.kv_cache_aware)
        if pool_mgr is not None:
            pool_mgr.emit = self.coordinator.note_cache
        backend = LiveBackend(self.perf, model_kv_time=model_kv_time)
        backend.kv_store = self.kv_store
        self.runtime = ServingRuntime(
            backend,
            self.coordinator, self.prefill_workers, self.decode_workers,
            chunk_tokens=policy.chunk_tokens)
        self.fleet = None
        if policy.autoscale:
            from repro.core.planner import Deployment, PlanLattice, \
                WorkerGroup
            from repro.runtime.autoscaler import AutoscaleConfig, \
                FleetController
            if lattice is None:   # structural fallback (same as the sim)
                d_chunk = (policy.decode_chunk_tokens[0]
                           if policy.decode_chunk_tokens else 0)
                lattice = PlanLattice.ratio(
                    Deployment((WorkerGroup(spec.tp, spec.n_prefill),),
                               (WorkerGroup(spec.tp, spec.n_decode,
                                            d_chunk),)),
                    span=policy.autoscale_span,
                    bucket_rates=policy.autoscale_buckets or (1.0,))
            self.fleet = self.runtime.fleet = FleetController(
                lattice,
                AutoscaleConfig(
                    span=policy.autoscale_span,
                    bucket_rates=tuple(lattice.bucket_rates),
                    window_s=policy.autoscale_window_s,
                    dwell_s=policy.autoscale_dwell_s,
                    swap_delay_s=policy.autoscale_swap_delay_s),
                runtime=self.runtime, coordinator=self.coordinator,
                spawn=self._fleet_spawn,
                # proc workers take their chunk size at spawn; only inproc
                # handles apply a new chunk to already-running workers
                apply_chunk=self._pool is None)

    def _link_topology(self) -> LinkTopology:
        """The measured topology the scheduler prices (DESIGN.md §16).

        In-process workers share one address space (every KV move is a
        device copy -> ``intra-process``); pool workers are separate
        processes whose hello-reported hosts distinguish ``intra-host``
        links from genuine ``cross-host`` ones."""
        if self._pool is None:
            return LinkTopology(colocated=True)
        return LinkTopology(hosts=dict(self._pool.worker_hosts),
                            colocated=False, default_host=self._pool.host)

    def _device_for(self, kind: str, idx: int):
        """Device of the in-process worker with stable id ``idx``: prefill
        workers take devices 0.., decode workers follow them, wrapping."""
        k = idx if kind == "prefill" else self.spec.n_prefill + idx
        return self.devices[k % len(self.devices)]

    def _probe_engine(self) -> Engine:
        if self._pool is None:
            return (self.prefill_workers[0].engine if self.prefill_workers
                    else self.decode_workers[0].engine)
        key = jax.random.PRNGKey(self._seed)
        return Engine(self.cfg, max_len=self._max_len, key=key,
                      tp=self.spec.tp)

    # -- lifecycle ---------------------------------------------------------
    def close(self) -> None:
        """Tear down worker processes (no-op for the inproc transport)."""
        if self._pool is not None:
            self._pool.close()

    def __enter__(self) -> "LiveCluster":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- public API -------------------------------------------------------
    @property
    def now(self) -> float:
        return self.runtime.now

    def submit(self, session: LiveSession) -> None:
        self.runtime.submit(session)

    def fail_worker(self, kind: str, idx: int, at: float) -> None:
        """Schedule a failure of the worker with STABLE id ``idx`` at
        logical time ``at`` — under the proc transport this is a real
        ``SIGKILL`` of the worker process."""
        self.runtime.schedule_failure(kind, idx, at)

    def set_straggler(self, kind: str, idx: int, speed: float) -> None:
        w = self.runtime.worker_by_id(kind, idx)
        if w is None:
            raise KeyError(f"no {kind} worker with id {idx}")
        w.speed = speed

    def add_prefill_worker(self):
        next_id = max((w.idx for w in self.prefill_workers), default=-1) + 1
        if self._pool is not None:
            w = self._pool.spawn("prefill", next_id)
        else:
            ref = (self.prefill_workers[0] if self.prefill_workers
                   else self.decode_workers[0])
            eng = Engine(self.cfg, max_len=ref.engine.max_len,
                         params=ref.engine.params, tp=self.spec.tp,
                         device=self._device_for("prefill", next_id))
            w = LivePrefillWorker(next_id, eng, tp=self.spec.tp)
        # keep the priced topology in step with the elastic scale-out —
        # on BOTH branches (the inproc topology is degenerate today, but a
        # scheduler pricing a stale topology is a silent wrong-cost bug)
        self.perf.topology = self._link_topology()
        self.runtime.register_worker(w, "prefill")
        return w

    def add_decode_worker(self, *, chunk_tokens: int = 0):
        """Elastic scale-up of the DECODE side (the half
        ``add_prefill_worker`` never covered): spawn at a fresh max-id+1
        stable id, with a planner-chosen per-worker chunk size."""
        next_id = max((w.idx for w in self.decode_workers), default=-1) + 1
        if self._pool is not None:
            w = self._pool.spawn("decode", next_id, chunk_tokens=chunk_tokens)
        else:
            ref = (self.decode_workers[0] if self.decode_workers
                   else self.prefill_workers[0])
            eng = Engine(self.cfg, max_len=ref.engine.max_len,
                         params=ref.engine.params, tp=self.spec.tp,
                         device=self._device_for("decode", next_id))
            w = LiveDecodeWorker(next_id, eng, max_slots=self.spec.max_slots,
                                 tp=self.spec.tp, chunk_tokens=chunk_tokens,
                                 packed=self.policy.packed)
        self.perf.topology = self._link_topology()
        self.runtime.register_worker(w, "decode")
        return w

    def _fleet_spawn(self, kind: str, chunk_tokens: int = 0):
        """FleetController scale-up hook (DESIGN.md §18)."""
        return (self.add_prefill_worker() if kind == "prefill"
                else self.add_decode_worker(chunk_tokens=chunk_tokens))

    def schedule_scale_up(self, at: float) -> None:
        """Explicit elastic resize through the FleetController: at ``at``,
        adopt the (fleet+1) lattice cell and spawn the missing worker."""
        assert self.fleet is not None, "requires policy.autoscale"
        self.runtime.events.at(
            at, lambda: self.fleet.scale_up(self.runtime.now), "scale-up")

    def run(self, sessions: List[LiveSession]) -> LiveResult:
        t_wall = time.perf_counter()
        for s in sessions:
            if s.session_id not in self.runtime.sessions:
                self.submit(s)
        self.runtime.run()
        wall = time.perf_counter() - t_wall
        return self._result(sessions, wall)

    def run_trace(self, sessions: List[LiveSession]) -> LiveResult:
        return self.run(sessions)

    def fit_promote(self) -> bool:
        """Refit ``PerfModel.kv_promote`` from the material store's timed
        host<->hbm page copies (DESIGN.md §17) — the measured counterpart
        of the modeled spill/promote bandwidth.  Returns True when samples
        existed; call between runs, never mid-trace (repricing mid-trace
        would fork the decision log from the modeled twin)."""
        if self.kv_store is None:
            return False
        samples = self.kv_store.promote_samples + self.kv_store.spill_samples
        if not samples:
            return False
        self.perf.fit_promote_from_bytes(samples)
        return True

    # -- results ------------------------------------------------------------
    def _result(self, sessions: List[LiveSession], wall: float) -> LiveResult:
        ttfts = [t for s in sessions for t in s.ttfts]
        itls = [t for s in sessions for t in s.itls]
        ok = sum(1 for s in sessions if self.slo.satisfied(s))
        kv = self.kv_path
        return LiveResult(
            sessions=sessions,
            slo_attainment=ok / max(len(sessions), 1),
            avg_ttft=mean(ttfts),
            avg_itl=mean(itls),
            p95_ttft=p95(ttfts),
            p95_itl=p95(itls),
            local_fraction=self.coordinator.local_fraction,
            rebinds=self.coordinator.rebinds,
            kv_bytes_moved=sum(w.kv_bytes_moved for w in self.prefill_workers),
            logical_time=self.now,
            wall_time=wall,
            steals=self.coordinator.sched.steals,
            preempts=self.coordinator.sched.preempts,
            migrations=self.coordinator.sched.migrations,
            kv_steal_bytes=getattr(self.runtime.backend,
                                   "kv_steal_bytes", 0),
            kv_migrate_bytes=getattr(self.runtime.backend,
                                     "kv_migrate_bytes", 0),
            transport=self.transport,
            kv_transfer_bytes=kv.bytes_moved if kv else 0,
            kv_transfer_ms=kv.ms if kv else 0.0,
            kv_transfers=kv.transfers if kv else 0,
            packed=any(getattr(w, "packed", False)
                       for w in self.decode_workers),
            fused_steps=sum(getattr(w, "fused_steps", 0)
                            for w in self.decode_workers),
            fused_ms=1e3 * sum(getattr(w, "fused_s", 0.0)
                               for w in self.decode_workers),
            tokens_uploaded=sum(
                w.engine.tokens_uploaded for w in
                (self.prefill_workers + self.decode_workers)
                if hasattr(w, "engine")),
            kv_pool=self.kv_store is not None,
            cache_hits=self.coordinator.sched.cache_hits,
            cache_hit_tokens=self.coordinator.sched.cache_hit_tokens,
            kv_spills=self.coordinator.sched.kv_spills,
            kv_promotes=self.coordinator.sched.kv_promotes,
            kv_hit_bytes=self.kv_store.hit_bytes if self.kv_store else 0,
            kv_spill_bytes=self.kv_store.spill_bytes if self.kv_store else 0,
            kv_promote_bytes=(self.kv_store.promote_bytes
                              if self.kv_store else 0),
            replans=self.coordinator.sched.replans,
            role_swaps=self.coordinator.sched.role_swaps,
            class_attainment=class_attainment(sessions, self.slo),
        )


def make_live_sessions(cfg: ModelConfig, *, num_sessions: int = 4,
                       rounds: int = 3, prefill_len: int = 24,
                       decode_len: int = 6, arrival_gap: float = 0.01,
                       seed: int = 0,
                       shared_prefix: int = 0,
                       tenants: Optional[List[str]] = None,
                       incr_len: Optional[int] = None,
                       ) -> List[LiveSession]:
    """Synthetic multi-round sessions over real token ids.

    Round 0 prompts ``prefill_len`` tokens; every later round appends an
    increment of ``incr_len`` tokens (default ``prefill_len``).

    ``shared_prefix``: the first N tokens of every round-0 prompt are drawn
    ONCE and shared verbatim across sessions (a common system prompt /
    tool schema), with a session-unique random tail after them — the
    shared-prefix structure the global KV pool dedups (DESIGN.md §17).
    Unique tails keep the sessions' page chains divergent from the first
    private token onward, so greedy decode cannot manufacture extra
    sharing the modeled twin would miss.

    ``tenants``: optional per-session tenant SLO-class labels, cycled over
    the session list (DESIGN.md §19)."""
    rng = np.random.default_rng(seed)
    shared = (rng.integers(0, cfg.vocab_size,
                           min(shared_prefix, prefill_len)).astype(np.int32)
              if shared_prefix > 0 else None)
    lens = [prefill_len if r == 0 or incr_len is None else incr_len
            for r in range(rounds)]
    out = []
    for sid in range(num_sessions):
        rs = [RoundSpec(prefill_len=n, decode_len=decode_len, env_delay=0.0)
              for n in lens]
        prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
                   for n in lens]
        if shared is not None:
            prompts[0] = np.concatenate(
                [shared, prompts[0][len(shared):]]).astype(np.int32)
        out.append(LiveSession(session_id=sid,
                               arrival_time=sid * arrival_gap,
                               rounds=rs, prompt_tokens=prompts,
                               tenant=(tenants[sid % len(tenants)]
                                       if tenants else "default")))
    return out
