"""Per-worker OS processes for the live cluster (DESIGN.md §13/§16).

Under ``LiveCluster(transport="proc"|"tcp")`` every prefill/decode worker is
a real child process owning its own JAX engine (its mesh slice — tp>1
children force their host-platform device count and build a tp-way mesh),
serving the engine surface over the RPC layer in ``repro.serving.rpc``:

    prefill_chunk   run one prefill chunk (optionally seeded with a
                    shipped history extract); returns the KV increment
    fused_step      Sarathi-style chunk + piggybacked decode batch
    decode_step     one continuous-batching step over fed slots
    kv_get / kv_put lazy history read / incremental KV write-back —
                    actual cache bytes over the socket, measured by
                    :class:`~repro.serving.kv_transfer.TransportKVPath`
    steal_handoff   work-stealing KV-locality accounting (§12)
    ping / shutdown liveness and graceful teardown

This module has both halves of the process boundary:

  * ``main()`` — the child: connect back to the coordinator's socket, send
    a hello, build the :class:`Engine` (deterministic params from the
    shared seed, so every process holds byte-identical weights — the
    multi-process equivalent of the in-process param sharing), then serve.
    The child wraps the stock :class:`LivePrefillWorker` /
    :class:`LiveDecodeWorker` around its engine, so the proc transport
    executes EXACTLY the code paths of the in-process transport — that is
    what makes decision-log and token parity a testable contract.
  * ``ProcPrefillWorker`` / ``ProcDecodeWorker`` — coordinator-side
    handles that duck-type the live workers (same scheduling-facing
    attributes; sessions and slot bookkeeping stay coordinator-side; only
    engine execution and cache bytes cross the boundary).
  * ``ProcWorkerPool`` — spawns children (``python -m
    repro.serving.worker_proc``), matches their hellos, and owns teardown;
    ``kill()`` on a handle is a real ``SIGKILL`` — the failure-injection
    path of ``LiveCluster.fail_worker`` under the proc transport.

The pool is transport-agnostic (§16): the transport registry
(``repro.serving.config``) supplies the coordinator's listen address
(AF_UNIX path vs TCP host:port) and each worker's hello carries its
hostname, so spawn/hello/teardown — and the KV link-class tagging on
``TransportKVPath`` — are shared verbatim between the proc and tcp
transports.  Off-host workers simply dial the advertised ``tcp:`` address;
anything the pool did not spawn itself can still be adopted by running the
child by hand with the same ``--socket`` spec.
"""
from __future__ import annotations

import argparse
import atexit
import dataclasses
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.configs.base import ModelConfig
from repro.core.types import PrefillTask
from repro.runtime.backend import WorkerDiedError
from repro.serving import rpc
from repro.serving.workers import (
    SlotBookkeeping,
    WorkerSchedState,
    resolve_packed,
)
from repro.serving.kv_transfer import (
    TransportKVPath,
    _numpy_tree,
    extract_range,
    insert_range,
    migrate_handoff,
    reshard,
    steal_handoff,
    transfer_bytes,
)

__all__ = ["ProcPrefillWorker", "ProcDecodeWorker", "ProcWorkerPool",
           "transport_available", "config_to_json", "config_from_json",
           "main"]


# ---------------------------------------------------------------------------
# config over the process boundary
# ---------------------------------------------------------------------------

def config_to_json(cfg: ModelConfig) -> str:
    return json.dumps(dataclasses.asdict(cfg))


def config_from_json(text: str) -> ModelConfig:
    d = json.loads(text)
    # JSON has no tuples; every sequence field on ModelConfig is tuple-typed
    d = {k: tuple(v) if isinstance(v, list) else v for k, v in d.items()}
    return ModelConfig(**d)


def transport_available(kind: str = "proc") -> bool:
    """Whether this host can run a multiprocess transport (subprocess spawn
    + the transport's socket family) — tests skip gracefully when it
    cannot."""
    if kind == "proc" and not hasattr(socket, "AF_UNIX"):
        return False
    if kind == "tcp":
        try:
            s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            s.bind(("127.0.0.1", 0))
            s.close()
        except OSError:
            return False
    try:
        subprocess.run([sys.executable, "-c", "pass"], timeout=60, check=True,
                       stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        return True
    except Exception:               # noqa: BLE001 — any spawn failure = no
        return False


# ---------------------------------------------------------------------------
# child side: the worker main loop
# ---------------------------------------------------------------------------

class _Shim:
    """Session stand-in inside the worker process: the coordinator owns the
    real session objects; engine code only needs slot / last_token /
    prompt_tokens, shipped per call."""
    __slots__ = ("session_id", "slot", "last_token", "prompt_tokens",
                 "context_len")

    def __init__(self, session_id=0, slot=None, last_token=0,
                 prompt_tokens=(), context_len=0):
        self.session_id = session_id
        self.slot = slot
        self.last_token = last_token
        self.prompt_tokens = list(prompt_tokens)
        self.context_len = context_len


def _chunk_task(tokens: np.ndarray, l_hist: int) -> PrefillTask:
    return PrefillTask(session_id=0, round_idx=0, l_hist=int(l_hist),
                       l_incr=len(tokens), enqueue_time=0.0, arrival_time=0.0)


def _prefill_handlers(worker):                       # pragma: no cover — runs
    """RPC surface of a prefill worker child."""     # in the child process
    import jax
    from repro.serving.workers import timed

    def prefill_chunk(tokens, l_hist, history=None):
        task = _chunk_task(tokens, l_hist)
        shim = _Shim(prompt_tokens=[np.asarray(tokens, np.int32)])
        dt, out = timed(worker.execute, task, shim, history_extract=history)
        return {"eng_s": dt,
                "increment": jax.device_get(out["increment"]),
                "logits": np.asarray(out["logits"])}

    def do_steal_handoff(l_hist):
        task = _chunk_task(np.empty(0, np.int32), l_hist)
        return int(steal_handoff(worker.engine.cfg, task, None, None, worker))

    def do_migrate_handoff(l_hist):
        task = _chunk_task(np.empty(0, np.int32), l_hist)
        return int(migrate_handoff(worker.engine.cfg, task, None, None,
                                   worker))

    return {"prefill_chunk": prefill_chunk, "steal_handoff": do_steal_handoff,
            "migrate_handoff": do_migrate_handoff}


def _decode_handlers(worker):                        # pragma: no cover — runs
    """RPC surface of a decode worker child."""      # in the child process
    import jax

    eng = worker.engine

    def _feed_slots(feed: Dict[int, int]) -> None:
        worker.slots = [None] * worker.max_slots
        for slot, last in feed.items():
            worker.slots[int(slot)] = _Shim(session_id=int(slot),
                                            slot=int(slot),
                                            last_token=int(last))

    def decode_step(feed):
        _feed_slots(feed)
        dt, toks = worker.decode_once()
        return {"eng_s": dt, "toks": toks}

    def fused_step(slot, tokens, feed):
        _feed_slots(feed)
        task = _chunk_task(tokens, 0)
        shim = _Shim(slot=int(slot),
                     prompt_tokens=[np.asarray(tokens, np.int32)])
        worker.slots[int(slot)] = shim
        dt, first, toks = worker.fused_step(task, shim,
                                            [s for s in worker.slots
                                             if s is not None and s is not shim])
        return {"eng_s": dt, "first": first, "toks": toks}

    def kv_put(slot, lo, tree):
        worker.cache = insert_range(worker.cache, reshard(tree, eng.device),
                                    eng.cfg, eng.max_len, int(lo), int(slot),
                                    replace_state=True)
        jax.block_until_ready(jax.tree.leaves(worker.cache)[0])
        return None

    def kv_get(slot, lo, hi):
        tree = extract_range(worker.cache, eng.cfg, eng.max_len, int(lo),
                             int(hi), row=int(slot))
        return jax.device_get(tree)

    def reset_slot(slot):
        worker.reset_slot(int(slot))
        return None

    return {"decode_step": decode_step, "fused_step": fused_step,
            "kv_put": kv_put, "kv_get": kv_get, "reset_slot": reset_slot}


def main(argv: Optional[List[str]] = None) -> None:  # pragma: no cover — the
    # child entry point is exercised end-to-end by tests/test_multiproc_*
    # in real subprocesses, which the coverage tracer does not follow.
    ap = argparse.ArgumentParser()
    ap.add_argument("--socket", required=True,
                    help="coordinator address spec: unix:<path>, "
                         "tcp:<host>:<port>, or a bare AF_UNIX path")
    ap.add_argument("--kind", choices=("prefill", "decode"), required=True)
    ap.add_argument("--idx", type=int, required=True)
    ap.add_argument("--cfg", required=True, help="ModelConfig as JSON")
    ap.add_argument("--max-len", type=int, required=True)
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree of this worker's mesh slice")
    ap.add_argument("--nodelay", type=int, default=1)
    ap.add_argument("--keepalive-s", type=float, default=0.0)
    ap.add_argument("--packed", type=int, default=-1,
                    help="ragged packed fused path: 1=on, 0=off, -1=auto")
    args = ap.parse_args(argv)

    sock = rpc.parse_address(args.socket).connect()
    rpc.tune_socket(sock, nodelay=bool(args.nodelay),
                    keepalive_s=args.keepalive_s)
    conn = rpc.RpcConn(sock)
    conn.send_msg({"hello": {"kind": args.kind, "idx": args.idx,
                             "pid": os.getpid(),
                             "host": socket.gethostname()}})

    import jax
    from repro.serving.engine import Engine
    from repro.serving.workers import LiveDecodeWorker, LivePrefillWorker

    cfg = config_from_json(args.cfg)
    # deterministic params from the shared seed: every worker process holds
    # byte-identical weights (the cross-process form of param sharing)
    engine = Engine(cfg, max_len=args.max_len,
                    key=jax.random.PRNGKey(args.seed), tp=args.tp)
    if args.kind == "prefill":
        worker = LivePrefillWorker(args.idx, engine, tp=args.tp)
        handlers = _prefill_handlers(worker)
    else:
        worker = LiveDecodeWorker(args.idx, engine, max_slots=args.max_slots,
                                  tp=args.tp,
                                  packed=(None if args.packed < 0
                                          else bool(args.packed)))
        handlers = _decode_handlers(worker)
    handlers["ping"] = lambda: {"ok": True, "pid": os.getpid(),
                                "kind": args.kind, "idx": args.idx}

    def shutdown():
        raise SystemExit(0)

    handlers["shutdown"] = shutdown
    rpc.serve(conn, handlers)


# ---------------------------------------------------------------------------
# coordinator side: worker handles
# ---------------------------------------------------------------------------

class _ProcWorkerBase(WorkerSchedState):
    """Coordinator-side view of one worker process.

    Shares the scheduling-facing surface with the in-process live workers
    (:class:`~repro.serving.workers.WorkerSchedState` — one definition, so
    the duck-typed contract cannot drift between transports); engine
    execution crosses the RPC boundary.  Measured durations are
    parent-side round-trips — serialization and socket time are *part of*
    the measured cost, which is the point of the proc transport."""

    def __init__(self, idx: int, client: rpc.RpcClient,
                 proc: subprocess.Popen, cfg: ModelConfig, max_len: int,
                 kv_path: TransportKVPath, tp: int = 1,
                 window_s: float = 10.0):
        self._init_sched_state(idx, tp, window_s)
        self.client = client
        self.proc = proc
        self.cfg = cfg
        self.max_len = max_len
        self.kv_path = kv_path

    # -- rpc ---------------------------------------------------------------
    def _call(self, method: str, **params):
        try:
            return self.client.call(method, **params)
        except WorkerDiedError:
            self.alive = False
            raise

    # -- process lifecycle ---------------------------------------------------
    @property
    def pid(self) -> int:
        return self.proc.pid

    def kill(self) -> None:
        """Hard failure injection: real SIGKILL, no goodbye."""
        self.alive = False
        self.client.dead = True
        self.client.close()
        if self.proc.poll() is None:
            try:
                os.kill(self.proc.pid, signal.SIGKILL)
            except OSError:
                pass
        try:
            self.proc.wait(timeout=10)
        except subprocess.TimeoutExpired:   # pragma: no cover — SIGKILL lands
            pass

    def shutdown(self) -> None:
        """Graceful teardown at cluster close."""
        if self.proc.poll() is None and not self.client.dead:
            self.client.notify("shutdown")
            self.client.close()
            try:
                self.proc.wait(timeout=10)
                return
            except subprocess.TimeoutExpired:  # pragma: no cover — hung child
                pass
        self.kill()


class ProcPrefillWorker(_ProcWorkerBase):
    kind = "prefill"

    def execute(self, task: PrefillTask, session, history_extract=None,
                cross_embeds=None) -> Dict:
        """Run one prefill chunk in the worker process; history KV ships
        with the request, the increment comes back with the response —
        real bytes both ways, accounted on the transport path."""
        if cross_embeds is not None:
            raise NotImplementedError(
                "cross-modal embeds are not supported over the proc "
                "transport yet (inproc only)")
        from repro.serving.workers import chunk_tokens_of
        tokens = np.asarray(chunk_tokens_of(task, session), np.int32)
        hist = None if history_extract is None else _numpy_tree(history_extract)
        t0 = time.perf_counter()
        out = self._call("prefill_chunk", tokens=tokens,
                         l_hist=int(task.l_hist), history=hist)
        round_trip = time.perf_counter() - t0
        moved = transfer_bytes(out["increment"])
        if hist is not None:
            moved += transfer_bytes(hist)
        self.kv_bytes_moved += moved
        # the KV share of this call's wall time: round trip minus the
        # engine's own compute (reported by the child)
        self.kv_path.account(moved, max(0.0, round_trip - out["eng_s"]),
                             link=self.kv_path.class_of(self.client))
        return {"increment": out["increment"], "logits": out["logits"]}

    def steal_handoff(self, task: PrefillTask, session=None) -> int:
        try:
            return int(self._call("steal_handoff", l_hist=int(task.l_hist)))
        except WorkerDiedError:
            # thief died between plan and handoff — account locally; the
            # runtime discovers the death on its next engine call
            return steal_handoff(self.cfg, task, session, None, self)

    def migrate_handoff(self, task: PrefillTask, session=None) -> int:
        # unlike steal_handoff, a WorkerDiedError here PROPAGATES: at this
        # point the chunk has already left the decode worker's queue, so
        # the runtime must learn of the death NOW and re-route the chunk
        # through the standard recovery path (the chaos suite SIGKILLs the
        # destination exactly here)
        return int(self._call("migrate_handoff", l_hist=int(task.l_hist)))


class ProcDecodeWorker(_ProcWorkerBase, SlotBookkeeping):
    kind = "decode"

    def __init__(self, idx: int, client: rpc.RpcClient,
                 proc: subprocess.Popen, cfg: ModelConfig, max_len: int,
                 kv_path: TransportKVPath, max_slots: int, tp: int = 1,
                 window_s: float = 10.0, chunk_tokens: int = 0,
                 packed: bool = False):
        super().__init__(idx, client, proc, cfg, max_len, kv_path, tp,
                         window_s)
        self.max_slots = max_slots
        self.chunk_tokens = chunk_tokens
        self.slots: List[Optional[object]] = [None] * max_slots
        self.mem_tokens = 0
        #: mirrors the child LiveDecodeWorker's resolved packed flag
        self.packed = packed
        self.fused_steps = 0
        self.fused_s = 0.0

    # -- slot management (free/occupancy/allocate/detach: SlotBookkeeping;
    #    bookkeeping is coordinator-side, the cache row lives worker-side) --
    def reset_slot(self, slot: int) -> None:
        self._call("reset_slot", slot=int(slot))

    def attach(self, session, increment: Dict, lo: int, first_token: int,
               n_tokens: int) -> None:
        if session.slot is None:
            self.allocate(session)
        self.kv_path.put(self.client, session.slot, lo, increment)
        session.last_token = first_token

    def history_extract(self, session) -> Dict:
        return self.kv_path.get(self.client, session.slot, 0,
                                session.context_len)

    def history_extract_range(self, session, lo: int, hi: int) -> Dict:
        """Partial history pull (DESIGN.md §17): only the miss suffix
        crosses the RPC socket — measured bytes shrink with the hit."""
        return self.kv_path.get(self.client, session.slot, int(lo), int(hi))

    # -- execution -----------------------------------------------------------
    def decode_once(self) -> Tuple[float, Dict[int, int]]:
        occupied = [i for i, s in enumerate(self.slots) if s is not None]
        if not occupied:
            return 0.0, {}
        feed = {i: int(self.slots[i].last_token) for i in occupied}
        t0 = time.perf_counter()
        out = self._call("decode_step", feed=feed)
        dt = time.perf_counter() - t0
        return dt, {int(k): int(v) for k, v in out["toks"].items()}

    def local_prefill(self, task: PrefillTask, session):
        dt, first, _ = self.fused_step(task, session, [])
        return dt, first

    def fused_step(self, task: PrefillTask, session, batch: List):
        from repro.serving.workers import chunk_tokens_of
        tokens = np.asarray(chunk_tokens_of(task, session), np.int32)
        feed = {int(b.slot): int(b.last_token) for b in batch}
        t0 = time.perf_counter()
        out = self._call("fused_step", slot=int(session.slot), tokens=tokens,
                         feed=feed)
        dt = time.perf_counter() - t0
        self.fused_steps += 1
        self.fused_s += dt
        by_slot = {int(k): int(v) for k, v in out["toks"].items()}
        toks = {b.session_id: by_slot[b.slot] for b in batch
                if b.slot in by_slot}
        return dt, int(out["first"]), toks


# ---------------------------------------------------------------------------
# spawn / teardown
# ---------------------------------------------------------------------------

def _src_root() -> str:
    """The directory that makes ``import repro`` work in a child."""
    here = os.path.abspath(os.path.dirname(__file__))   # .../src/repro/serving
    return os.path.dirname(os.path.dirname(here))       # .../src


def _child_env(tp: int) -> Dict[str, str]:
    """Environment of a worker child: ``repro`` importable, and JAX pinned
    to the parent's platform (a child never drops to another backend)."""
    import jax
    env = os.environ.copy()
    env["PYTHONPATH"] = _src_root() + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = jax.default_backend()
    if tp > 1:
        # a tp-way mesh needs tp devices; on the CPU platform force the
        # host device count BEFORE the child imports jax (the same trick
        # the dry-run entrypoint uses for production-scale meshes)
        flag = f"--xla_force_host_platform_device_count={tp}"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + flag).strip()
    return env


class ProcWorkerPool:
    """Owns the coordinator socket and every spawned worker process.

    Transport-agnostic (§16): the listen address comes from the transport
    registry (AF_UNIX path for ``proc``, host:port for ``tcp``), children
    get the dial spec on their command line, and everything else — hello
    matching, RPC clients, SIGKILL/teardown — is shared."""

    def __init__(self, cfg: ModelConfig, *, max_len: int, max_slots: int = 4,
                 seed: int = 0, rpc_timeout_s: Optional[float] = None,
                 spawn_timeout_s: Optional[float] = None,
                 kv_path: Optional[TransportKVPath] = None,
                 packed: Optional[bool] = None,
                 transport: Optional[object] = None, tp: int = 1):
        from repro.serving.config import (
            TRANSPORT_REGISTRY, resolve_transport)
        tcfg = resolve_transport(transport if transport is not None
                                 else "proc")
        if rpc_timeout_s is not None:
            tcfg = tcfg.replace(rpc_timeout_s=rpc_timeout_s)
        if spawn_timeout_s is not None:
            tcfg = tcfg.replace(spawn_timeout_s=spawn_timeout_s)
        entry = TRANSPORT_REGISTRY[tcfg.kind]
        if not entry.multiprocess:
            raise ValueError(
                f"transport {tcfg.kind!r} does not spawn worker processes")
        import jax
        if jax.default_backend() == "tpu":
            # a chip belongs to one process, and this one already holds it
            raise RuntimeError(
                f"the {tcfg.kind!r} transport starts one JAX process per "
                "worker, but this process already holds the TPU; on a chip "
                "host serve with transport='inproc'")
        resolve_packed(cfg, packed)     # refuse here, not in every child
        self.cfg = cfg
        self.max_len = max_len
        self.max_slots = max_slots
        self.packed = packed
        self.seed = seed
        self.tp = tp
        self.transport = tcfg
        self._entry = entry
        self.rpc_timeout_s = tcfg.rpc_timeout_s
        self.spawn_timeout_s = tcfg.spawn_timeout_s
        self.kv_path = kv_path or TransportKVPath()
        self.kv_path.default_class = entry.link_class
        self.host = socket.gethostname()
        #: (kind, idx) -> hello-reported hostname, for LinkTopology
        self.worker_hosts: Dict[Tuple[str, int], str] = {}
        self.workers: List[_ProcWorkerBase] = []
        self._dir = tempfile.mkdtemp(prefix="repro-cluster-")
        addr = entry.make_address(tcfg, self._dir)
        self._listener = addr.listen(64)
        if isinstance(addr, rpc.TcpAddress):
            addr = addr.bound(self._listener)    # resolve ephemeral port
        self.address = addr
        #: the spec children dial — an operator can advertise a routable
        #: host for genuinely off-host workers
        self.dial_spec = tcfg.advertise or addr.spec
        self._listener.settimeout(self.spawn_timeout_s)
        self._closed = False
        atexit.register(self.close)

    # -- spawning ------------------------------------------------------------
    def _launch(self, kind: str, idx: int) -> subprocess.Popen:
        env = _child_env(self.tp)
        log = open(os.path.join(self._dir, f"{kind}{idx}.log"), "wb")
        cmd = [sys.executable, "-m", "repro.serving.worker_proc",
               "--socket", self.dial_spec, "--kind", kind,
               "--idx", str(idx), "--cfg", config_to_json(self.cfg),
               "--max-len", str(self.max_len),
               "--max-slots", str(self.max_slots), "--seed", str(self.seed),
               "--tp", str(self.tp),
               "--nodelay", str(int(self.transport.nodelay)),
               "--keepalive-s", str(self.transport.keepalive_s),
               "--packed",
               str(-1 if self.packed is None else int(self.packed))]
        try:
            return subprocess.Popen(cmd, env=env, stdout=log,
                                    stderr=subprocess.STDOUT)
        finally:
            log.close()

    def _log_tail(self, kind: str, idx: int, n: int = 2000) -> str:
        try:
            with open(os.path.join(self._dir, f"{kind}{idx}.log"), "rb") as fh:
                return fh.read()[-n:].decode(errors="replace")
        except OSError:
            return "<no log>"

    def spawn_many(self, specs: List[Tuple[str, int, int]]
                   ) -> List[_ProcWorkerBase]:
        """Spawn ``(kind, idx, chunk_tokens)`` workers concurrently (engine
        import dominates startup; children overlap it) and match hellos."""
        procs = {(k, i): self._launch(k, i) for k, i, _ in specs}
        chunks = {(k, i): c for k, i, c in specs}
        out: Dict[Tuple[str, int], _ProcWorkerBase] = {}
        deadline = time.monotonic() + self.spawn_timeout_s
        while len(out) < len(specs):
            try:
                self._listener.settimeout(max(1.0, deadline - time.monotonic()))
                conn, _ = self._listener.accept()
            except socket.timeout:
                self._abort_spawn(procs, out)
                missing = [ki for ki in procs if ki not in out]
                raise RuntimeError(
                    f"worker processes failed to start: {missing}; log tail: "
                    + self._log_tail(*missing[0])) from None
            # accepted sockets do NOT inherit the listener's timeout: bound
            # the hello read too, or a child wedged between connect() and
            # its hello would hang the spawn past the deadline
            conn.settimeout(max(1.0, deadline - time.monotonic()))
            rpc.tune_socket(conn, nodelay=self.transport.nodelay,
                            keepalive_s=self.transport.keepalive_s)
            client_probe = rpc.RpcConn(conn)
            try:
                hello, _ = client_probe.recv_msg()
            except (socket.timeout, ConnectionError, OSError):
                client_probe.close()
                continue            # count against the spawn deadline
            kind, idx = hello["hello"]["kind"], hello["hello"]["idx"]
            worker_host = hello["hello"].get("host", self.host)
            proc = procs[(kind, idx)]
            client = rpc.RpcClient(conn, kind, idx, timeout_s=self.rpc_timeout_s)
            # link class of this worker's coordinator link: the registry's
            # class for same-host children, cross-host for a worker whose
            # hello names another machine (it dialed the advertised address)
            link = (self._entry.link_class if worker_host == self.host
                    else "cross-host")
            self.worker_hosts[(kind, idx)] = worker_host
            self.kv_path.tag(kind, idx, link)
            if kind == "prefill":
                w = ProcPrefillWorker(idx, client, proc, self.cfg,
                                      self.max_len, self.kv_path, tp=self.tp)
            else:
                w = ProcDecodeWorker(idx, client, proc, self.cfg,
                                     self.max_len, self.kv_path,
                                     max_slots=self.max_slots, tp=self.tp,
                                     chunk_tokens=chunks[(kind, idx)],
                                     packed=resolve_packed(self.cfg,
                                                           self.packed))
            w.host = worker_host
            w.link_class = link
            out[(kind, idx)] = w
            self.workers.append(w)
        return [out[(k, i)] for k, i, _ in specs]

    def spawn(self, kind: str, idx: int, *, chunk_tokens: int = 0
              ) -> _ProcWorkerBase:
        return self.spawn_many([(kind, idx, chunk_tokens)])[0]

    def _abort_spawn(self, procs, matched) -> None:
        for ki, p in procs.items():
            if ki not in matched and p.poll() is None:
                p.kill()

    # -- teardown ------------------------------------------------------------
    def close(self) -> None:
        # a pool refused in __init__ never opened anything
        if getattr(self, "_closed", True):
            return
        self._closed = True
        for w in self.workers:
            try:
                w.shutdown()
            except Exception:       # noqa: BLE001 — teardown is best-effort
                pass
        try:
            self._listener.close()
        except OSError:
            pass
        shutil.rmtree(self._dir, ignore_errors=True)

    def __del__(self):              # pragma: no cover — gc-order dependent
        self.close()


if __name__ == "__main__":          # pragma: no cover — child entry point
    main()
