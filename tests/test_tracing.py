"""Spans and counters of the serving path (``repro.runtime.tracing``).

A tiny model is served through ``LiveCluster(inproc)`` on the CPU under a
profiler session, once with every prefill remote (history reads, KV
moves, work stealing) and once with every prefill local (fused steps,
decode-local offload).  The spans must land on the trace's host plane with
their ids and nesting, and the counters the benchmark reads must hold what
they claim: queue wait per launch, completion lag (0 on the logical loop),
host time per step, KV time.
"""
import glob
import heapq
import os
import re
import time
from pathlib import Path

import jax
import pytest

from repro.configs import get_config
from repro.core import (
    Deployment,
    PerfModel,
    SimConfig,
    Simulation,
    SLOSpec,
    WorkerGroup,
)
from repro.core.routing import local_first_routing
from repro.runtime import EventLoop
from repro.runtime.tracing import SPANS
from repro.serving import (ClusterSpec, LiveCluster, SchedPolicy,
                           make_live_sessions)
from repro.workloads import make_trace

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"

#: spans that carry the request's ids
REQUEST_SPANS = ("repro.route", "repro.prefill", "repro.join", "repro.fused")
#: child span -> the spans it may sit directly inside
PARENTS = {
    "repro.kv.read_history": ("repro.prefill",),
    "repro.kv.insert_history": ("repro.prefill",),
    "repro.prefill.chunk": ("repro.prefill",),
    "repro.kv.extract": ("repro.prefill",),
    "repro.prefill.logits": ("repro.prefill",),
    "repro.kv.attach": ("repro.join",),
    "repro.decode.feed": ("repro.decode.step", "repro.fused"),
    "repro.decode.launch": ("repro.decode.step", "repro.fused"),
    "repro.decode.wait": ("repro.decode.step", "repro.fused"),
    "repro.decode.sample": ("repro.decode.step", "repro.fused"),
}


def _cluster(cfg, **policy):
    return LiveCluster(cfg, spec=ClusterSpec(n_prefill=2, n_decode=1,
                                             max_slots=4, max_len=128),
                       policy=SchedPolicy(**policy), slo=SLOSpec(10.0, 10.0),
                       seed=0, profile=False)


def _instrument(cl, log):
    """Record, at every launch, the runtime's clock less the task's enqueue
    time, and the wall time of every decode-worker step call."""
    rt, be = cl.runtime, cl.runtime.backend
    run_prefill, run_fused = be.run_prefill, be.run_fused_prefill

    def prefill(w, task, *a):
        log["waits"].append(((w.kind, w.idx), rt.now - task.enqueue_time))
        return run_prefill(w, task, *a)

    def fused(w, task, *a):
        log["waits"].append(((w.kind, w.idx), rt.now - task.enqueue_time))
        return run_fused(w, task, *a)

    be.run_prefill, be.run_fused_prefill = prefill, fused
    for d in cl.decode_workers:
        def dense(*a, _f=d.engine.decode_step, _d=d):
            log["ctx"].append(sum(s.context_len for s in _d.slots
                                  if s is not None))
            return _f(*a)
        d.engine.decode_step = dense
        for name in ("decode_once", "fused_step"):
            def call(*a, _f=getattr(d, name), _d=d):
                h0, t0 = _d.host_s, time.perf_counter()
                out = _f(*a)
                log["steps"].append((time.perf_counter() - t0,
                                     _d.host_s - h0))
                return out
            setattr(d, name, call)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    cfg = get_config("qwen2.5-14b").reduced()
    remote = _cluster(cfg, scheduler="dynamo", work_stealing=True)
    local = _cluster(cfg, scheduler="ampd-chunked", chunk_tokens=8,
                     decode_offload=True)
    local.coordinator.routing = local_first_routing(10.0, 10.0)
    logs = {}
    out = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(out))
    try:
        for name, cl in (("remote", remote), ("local", local)):
            logs[name] = {"waits": [], "steps": [], "ctx": []}
            _instrument(cl, logs[name])
            cl.run_trace(make_live_sessions(cfg, num_sessions=3, rounds=2,
                                            prefill_len=16, decode_len=4))
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    return {"remote": remote, "local": local, "logs": logs,
            "spans": _host_spans(path)}


def _host_spans(path):
    """(name, start_ns, end_ns, stats, line) of every ``repro.*`` event on
    the host planes."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats), (plane.name, k)))
    return out


def test_spans_name_every_emitted_span():
    """``SPANS`` is exactly the set of span names the source emits."""
    emitted = set()
    for f in SRC.rglob("*.py"):
        emitted |= set(re.findall(
            r'(?:span|kv_op)\([^)]*?"(repro\.[a-z_.]+)"', f.read_text()))
    assert emitted == set(SPANS)
    assert len(SPANS) == len(set(SPANS))


def test_served_path_emits_every_span(served):
    seen = {s[0] for s in served["spans"]}
    assert set(SPANS) <= seen, sorted(set(SPANS) - seen)


def test_request_spans_carry_session_and_round(served):
    sessions = {s.session_id for s in served["remote"].runtime.sessions.values()}
    for name, _, _, stats, _ in served["spans"]:
        if name in REQUEST_SPANS:
            assert stats["session"] in sessions, (name, stats)
            assert stats["round"] in (0, 1), (name, stats)
    steps = [st for n, *_, st, _ in served["spans"] if n == "repro.decode.step"]
    assert steps and all(1 <= st["rows"] <= 4 for st in steps)


def test_children_lie_inside_their_parent(served):
    spans = served["spans"]
    for name, lo, hi, _, line in spans:
        if name not in PARENTS:
            continue
        assert any(p in PARENTS[name] and pl == line and plo <= lo
                   and hi <= phi for p, plo, phi, _, pl in spans), name


def test_completion_lag_is_zero_on_the_logical_loop(served):
    for key in ("remote", "local"):
        cl = served[key]
        d = cl.decode_workers[0]
        assert d.steps > 0
        for w in cl.prefill_workers + cl.decode_workers:
            assert w.lag_s == 0.0, (key, w.kind, w.idx, w.lag_s)


def test_queue_wait_is_launch_less_enqueue(served):
    for key in ("remote", "local"):
        cl, waits = served[key], served["logs"][key]["waits"]
        assert waits
        for w in cl.prefill_workers + cl.decode_workers:
            mine = [x for k, x in waits if k == (w.kind, w.idx)]
            assert w.launches == len(mine)
            assert w.queue_wait_s == pytest.approx(sum(mine))
    # local-first routing launches on the decode worker only
    assert served["local"].decode_workers[0].launches > 0


def test_host_time_within_step_and_kv_time_counted(served):
    for key in ("remote", "local"):
        steps = served["logs"][key]["steps"]
        assert steps
        assert all(0.0 <= host <= wall for wall, host in steps)
        assert any(host > 0.0 for _, host in steps)
    remote = served["remote"]
    # later rounds read their history off the decode worker: the prefill
    # side pays the read, insert and extract; the decode side the attach
    assert any(w.kv_s > 0.0 for w in remote.prefill_workers)
    assert remote.decode_workers[0].kv_s > 0.0


def test_decode_ctx_tokens_sums_context_of_dense_steps(served):
    """The counter adds each dense decode step's occupied rows' cached
    context, and nothing else."""
    for key in ("remote", "local"):
        ctx = served["logs"][key]["ctx"]
        assert ctx and sum(ctx) > 0, key
        assert served[key].decode_workers[0].decode_ctx_tokens == sum(ctx)
    fresh = _cluster(get_config("qwen2.5-14b").reduced())
    assert fresh.decode_workers[0].decode_ctx_tokens == 0


def _simulation():
    ss = make_trace("hotpotqa", num_sessions=20, arrival_rate=2.0, seed=1)
    return ss, Simulation(
        PerfModel(get_config("qwen3-32b")),
        Deployment((WorkerGroup(4, 2),), (WorkerGroup(4, 2),)), ss,
        SLOSpec(ttft_thres=3.0, itl_thres=0.15), SimConfig(scheduler="ampd"))


class _LateLoop(EventLoop):
    """Runs every step completion ``LATE`` seconds after it was due, as a
    busy host thread would."""
    LATE = 0.01

    def run(self):
        while self._heap:
            t, _, fn, label = heapq.heappop(self._heap)
            self.now = max(self.now, t)
            if label in ("prefill-done", "decode-step", "fused-step"):
                self.now += self.LATE
            fn()
        return self.now


def test_lag_counts_late_completions():
    _, sim = _simulation()
    late = _LateLoop()
    late._heap, late._seq = sim.runtime.events._heap, sim.runtime.events._seq
    sim.runtime.events = late
    sim.run()
    workers = sim.prefill_workers + sim.decode_workers
    assert sum(w.steps for w in workers) > 0
    for w in workers:
        assert w.lag_s >= _LateLoop.LATE * w.steps - 1e-9, (w.kind, w.idx)


def test_modeled_counters():
    """The modeled backend shares the runtime: completion lag is 0, and
    every launch counts its queue wait."""
    ss, sim = _simulation()
    sim.run()
    workers = sim.prefill_workers + sim.decode_workers
    assert sum(w.launches for w in workers) >= sum(
        s.num_rounds for s in ss)
    assert sum(w.steps for w in workers) > 0
    assert all(w.lag_s == 0.0 and w.queue_wait_s >= 0.0 for w in workers)
    assert sum(w.queue_wait_s for w in workers) > 0.0
