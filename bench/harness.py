"""One run of one benchmark cell on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Phases: build the cluster (weights from the seed, the engines' own
profiling), warm up exactly the shapes the cell's traffic uses, start the
open-loop traffic on the wall clock and let it ramp, measure ``--seconds``,
let the rounds due in the window drain, then check what was served against
the configuration's plain reference.  The last line of standard output is
one JSON object; the lines before it, and standard error, carry the rest.

Everything a cell is made of is found by name: ``configs/<config>.json``
(with its architecture module ``configs/<reference>.py``, see
``bench/model.py``), ``traffic/<traffic>.json`` and ``metrics/<metric>.py``,
in the directories of ``search``.  A reader lists the program counters it
reads in ``COUNTERS``, each as ``<role>.<attribute>``; the harness takes
those at the window's edges.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
TRACE_SECONDS = 6.0


def process_age_s() -> float:
    """Seconds since this process started (Linux), else since import."""
    try:
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return up - start / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _IMPORTED


_IMPORTED = time.perf_counter()


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Discovery by name
# ---------------------------------------------------------------------------

def find(kind: str, name: str, suffix: str, search: Sequence[Path]) -> Path:
    for d in search:
        p = Path(d) / kind / f"{name}{suffix}"
        if p.is_file():
            return p
    raise FileNotFoundError(f"no {kind}/{name}{suffix} under "
                            f"{[str(d) for d in search]}")


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_config(name: str, search) -> dict:
    c = json.loads(find("configs", name, ".json", search).read_text())
    c["name"] = name
    c["architecture"] = str(find("configs", c["reference"], ".py", search))
    return c


# ---------------------------------------------------------------------------
# What a run measured
# ---------------------------------------------------------------------------

@dataclass
class RoundRecord:
    session: int
    index: int
    due: float                       # loop seconds the round was due
    output: int                      # tokens the round should emit
    first: Optional[float] = None    # loop seconds of its first token
    times: List[float] = field(default_factory=list)   # every token, in order


@dataclass
class Run:
    """Everything a metric reader may look at."""
    config: dict
    mix: dict
    cell: dict
    seconds: float
    w0: float
    w1: float
    drain_end: float
    device_kind: str
    setup_s: float
    rounds: List[RoundRecord]            # rounds due in [w0, w1)
    all_rounds: List[RoundRecord]
    recorder: object
    counters: Dict[str, Tuple[float, float]]    # role.name -> (at w0, at w1)
    compile_times: List[float]
    trace: object = None                 # bench.trace.Trace, traced runs
    trace_window: Optional[Tuple[float, float]] = None   # loop seconds

    @property
    def slo(self) -> dict:
        return self.mix["slo"]

    def delta(self, name: str) -> float:
        a, b = self.counters[name]
        return b - a


def percentile(vals: Sequence[float], q: float) -> float:
    """Nearest-rank upper percentile, clamped (the runtime's definition)."""
    s = sorted(vals)
    return s[min(len(s) - 1, int(q * len(s)))]


# ---------------------------------------------------------------------------
# Build and warm-up
# ---------------------------------------------------------------------------

def build(c: dict, mix: dict, seed: int, device):
    """The cluster as a user builds it, serving the benchmark's weights."""
    import jax
    import repro.serving.cluster as cluster_mod
    from repro.core.types import SLOSpec
    from repro.serving.config import ClusterSpec, SchedPolicy

    from bench.model import engine_with_params, make_params, model_config

    times = {}
    t = time.perf_counter()
    params = make_params(c, seed, device)
    jax.block_until_ready(params)
    times["weights_s"] = time.perf_counter() - t

    real_engine, real_profile = cluster_mod.Engine, cluster_mod.profile_engine
    prof = {"s": 0.0}

    def profile(*a, **k):
        t0 = time.perf_counter()
        out = real_profile(*a, **k)
        prof["s"] += time.perf_counter() - t0
        return out

    slo = mix["slo"]
    cluster_mod.Engine = engine_with_params(real_engine, params)
    cluster_mod.profile_engine = profile
    t = time.perf_counter()
    try:
        cl = cluster_mod.LiveCluster(
            model_config(c),
            spec=ClusterSpec(n_prefill=1, n_decode=1,
                             max_slots=c["serving"]["decode_slots"],
                             max_len=c["serving"]["max_len"]),
            transport="inproc", policy=SchedPolicy(),
            slo=SLOSpec(ttft_thres=slo["ttft_s"], itl_thres=slo["itl_s"],
                        ttit_thres=slo["ttit_s"]),
            seed=int(seed) % (1 << 31), profile=True, devices=[device])
    finally:
        cluster_mod.Engine, cluster_mod.profile_engine = real_engine, real_profile
    times["profile_s"] = prof["s"]
    times["build_s"] = time.perf_counter() - t - prof["s"]
    return cl, times


def history_lengths(rounds) -> List[int]:
    """Cached context before each later round of one session."""
    out, ctx = [], 0
    for r in rounds[:-1]:
        ctx += r.prompt + r.output - 1
        out.append(ctx)
    return out


def warm_up(cl, shapes) -> Dict[str, int]:
    """Run every program and every per-length copy the traffic will use,
    through the workers' own calls where a call owns the shape."""
    from repro.core.types import PrefillTask, RoundSpec
    from repro.serving.kv_transfer import extract_range, insert_range, reshard
    from repro.serving.workers import LiveSession

    pw, dw = cl.prefill_workers[0], cl.decode_workers[0]
    pe = pw.engine
    cfg, M = pe.cfg, pe.max_len
    prompts = sorted({r.prompt for s in shapes for r in s})
    hists = sorted({h for s in shapes for h in history_lengths(s)})

    def session(sid: int, n: int) -> LiveSession:
        return LiveSession(sid, 0.0, [RoundSpec(n, 3, 0.0)],
                           [np.ones(n, np.int32)])

    def task(sid: int, n: int, hist: int = 0) -> PrefillTask:
        return PrefillTask(sid, 0, hist, n, 0.0, 0.0, is_initial=hist == 0)

    for i, n in enumerate(prompts):
        # remote prefill, its increment's move into a decode slot, and the
        # same prompt as a local (packed) prefill on the decode worker
        s = session(-1 - i, n)
        out = pw.execute(task(s.session_id, n), s)
        dw.attach(s, out["increment"], 0, int(np.argmax(out["logits"])), n)
        dw.detach(s)
        s = session(-1 - i, n)
        dw.allocate(s)
        dw.local_prefill(task(s.session_id, n), s)
        dw.detach(s)
    for h in hists:
        # lazy history read off a decode row into a fresh prefill cache
        ext = extract_range(dw.cache, cfg, M, 0, h, row=0)
        fresh = insert_range(pe.new_cache(1), reshard(ext, pe.device), cfg,
                             M, 0, 0, replace_state=True)
        del ext, fresh
    for slot in range(dw.max_slots):
        dw.reset_slot(slot)
        extract_range(dw.cache, cfg, M, 0, 8, row=slot)
    # decode steps: one row (packed) and several rows (dense)
    for rows in (1, 2):
        live = [session(-100 - j, 8) for j in range(rows)]
        for s in live:
            dw.allocate(s)
            s.last_token = 1
        dw.decode_once()
        for s in live:
            dw.detach(s)
    for slot in range(dw.max_slots):
        dw.reset_slot(slot)
    pw.kv_bytes_moved = 0
    return {"prompt_lengths": len(prompts), "history_lengths": len(hists)}


# ---------------------------------------------------------------------------
# The measured run
# ---------------------------------------------------------------------------

#: the cluster's objects that hold each role's counters
ROLES = {"coordinator": lambda cl: [cl.coordinator],
         "prefill": lambda cl: cl.prefill_workers,
         "decode": lambda cl: cl.decode_workers}


def declared_counters(readers) -> List[str]:
    """Every counter the readers list in ``COUNTERS``."""
    return sorted({n for r in readers for n in getattr(r, "COUNTERS", ())})


def _counters(cl, names: Sequence[str]) -> Dict[str, float]:
    """Each counter ``<role>.<attribute>``: the attribute summed over the
    role's objects, 0 on an object that lacks it."""
    out = {}
    for n in names:
        role, attr = n.split(".", 1)
        out[n] = sum(getattr(o, attr, 0) for o in ROLES[role](cl))
    return out


def _install(cl):
    """The wall-clock loop and recorder of this cluster, made once."""
    import jax

    from bench.clock import WallClockLoop
    from bench.probes import Recorder

    if not hasattr(cl, "_bench"):
        loop = WallClockLoop()
        cl.runtime.events = loop
        rec = Recorder(loop)
        rec.install(cl)
        loop.start()
        compiles: List[float] = []
        t0 = time.perf_counter() - loop.wall()

        # the listener outlives the run: it holds only the list, never the
        # loop (whose events reach the program's device buffers)
        def on_compile(event: str, duration: float, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                compiles.append(time.perf_counter() - t0)

        jax.monitoring.register_event_duration_secs_listener(on_compile)
        cl._bench = (loop, rec, compiles)
    return cl._bench


def serve(cl, c: dict, mix: dict, seed: int, seconds: float,
          trace: bool = False, trace_dir: Optional[Path] = None,
          sid_base: int = 0, finish: bool = False,
          counters: Sequence[str] = ()):
    """Offer the traffic on the wall clock, measure, drain, taking the
    ``counters`` at the window's edges.  ``finish`` runs on until every
    session is done, so the cluster is idle again."""
    import jax
    from repro.core.types import RoundSpec
    from repro.serving.workers import LiveSession

    from bench import sessions

    loop, rec, compiles = _install(cl)
    max_len = c["serving"]["max_len"]
    plans = sessions.plan(mix, seconds, seed, c["vocab_size"], max_len)
    t_base = loop.wall()
    for p in plans:
        p.index += sid_base
        p.arrival += t_base
        cl.submit(LiveSession(
            session_id=p.index, arrival_time=p.arrival,
            rounds=[RoundSpec(r.prompt, r.output - 1, r.env_delay)
                    for r in p.rounds],
            prompt_tokens=p.prompts))

    w0 = t_base + float(mix["ramp_s"])
    w1 = w0 + seconds
    never = lambda: False  # noqa: E731
    loop.run_until(never, w0)
    setup_s = process_age_s()
    at_w0 = _counters(cl, counters)
    trace_window = None
    if trace:
        rec.annotate = True
        jax.profiler.start_trace(str(trace_dir))
        t_lo = loop.wall()
        with jax.profiler.TraceAnnotation("bench:window"):
            loop.run_until(never, min(w1, w0 + TRACE_SECONDS))
        t_hi = loop.wall()
        jax.profiler.stop_trace()
        rec.annotate = False
        trace_window = (t_lo, t_hi)
    loop.run_until(never, w1)
    at_w1 = _counters(cl, counters)

    # rounds due in the window: round 0 at its arrival, round k at round
    # k-1's last token plus its environment delay
    by_sid = {p.index: p for p in plans}
    due: Dict[Tuple[int, int], float] = {(p.index, 0): p.arrival
                                         for p in plans}
    for (sid, r), lg in list(rec.rounds.items()):
        p = by_sid.get(sid)
        if (p is not None and r + 1 < len(p.rounds)
                and len(lg.tokens) >= p.rounds[r].output - 1):
            end = lg.tokens[-1][0] if lg.tokens else lg.first[0]
            due[(sid, r + 1)] = end + p.rounds[r].env_delay
    in_window = [k for k, t in due.items() if w0 <= t < w1]
    slo = mix["slo"]
    longest = max((by_sid[s].rounds[r].output for s, r in in_window),
                  default=0)
    drain_end = w1 + slo["ttft_s"] + longest * slo["itl_s"]

    def drained() -> bool:
        for sid, r in in_window:
            lg = rec.rounds.get((sid, r))
            if (lg is None or lg.first is None
                    or len(lg.tokens) < by_sid[sid].rounds[r].output - 1):
                return False
        return True

    loop.run_until(drained, drain_end)
    drain_end = min(drain_end, loop.wall())

    records: Dict[Tuple[int, int], RoundRecord] = {}
    for (sid, r), t in due.items():
        lg = rec.rounds.get((sid, r))
        rr = RoundRecord(sid, r, t, by_sid[sid].rounds[r].output)
        if lg is not None and lg.first is not None:
            rr.first = lg.first[0]
            rr.times = [lg.first[0]] + [x for x, _ in lg.tokens]
        records[(sid, r)] = rr
    if finish:
        done = lambda: all(  # noqa: E731
            cl.runtime.sessions[p.index].state == "done" for p in plans)
        loop.run_until(done, loop.wall() + 600.0)
    return dict(loop=loop, recorder=rec, plans=plans, records=records,
                in_window=in_window, w0=w0, w1=w1, drain_end=drain_end,
                setup_s=setup_s, counters={k: (at_w0[k], at_w1[k])
                                           for k in at_w0},
                compiles=list(compiles), trace_window=trace_window)


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def use_compile_cache(jax) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    holding every program (eager copies too), never evicting."""
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def parse(argv):
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def metric_readers(names: Sequence[str], search) -> Dict[str, object]:
    return {n: load_module(find("metrics", n, ".py", search)) for n in names}


def main(argv=None, *, require_tpu: bool = True,
         search: Sequence[Path] = (BENCH_DIR,),
         benchmark: Optional[dict] = None, out_dir: Optional[Path] = None,
         cache: bool = True) -> int:
    args = parse(argv)
    bm = benchmark or json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bm["workloads"]}
    if args.workload not in cells:
        log(f"unknown workload {args.workload!r}; known: {sorted(cells)}")
        return 2
    cell = cells[args.workload]
    out_dir = Path(out_dir or ROOT / ".bench_out")
    out_dir.mkdir(parents=True, exist_ok=True)

    import jax
    if cache:
        use_compile_cache(jax)

    devices = jax.devices()
    dev = devices[0]
    log(f"platform={dev.platform} device_kind={dev.device_kind} "
        f"device_count={len(devices)}")
    if require_tpu and dev.platform != "tpu":
        log("no TPU found: this benchmark measures the chip and never "
            "falls back to the CPU")
        return 3
    if len(devices) < int(cell["chips"]):
        log(f"cell needs {cell['chips']} chips, found {len(devices)}")
        return 3

    c = load_config(cell["config"], search)
    mix = json.loads(find("traffic", cell["traffic"], ".json",
                          search).read_text())
    phase = "end_to_end" if not args.trace else "per_layer"
    wanted = [m for m in bm[phase]
              if args.workload in m.get("workloads", [args.workload])]
    readers = metric_readers([m["name"] for m in wanted], search)

    from bench import sessions
    cl, times = build(c, mix, args.seed, dev)
    log(f"profiled: prefill {cl.perf.pre[1]} decode {cl.perf.dec[1]}")
    t = time.perf_counter()
    shapes = sessions.shape_set(mix, args.seconds, c["serving"]["max_len"])
    warm = warm_up(cl, shapes)
    times["warm_up_s"] = time.perf_counter() - t

    trace_dir = out_dir / "trace"
    if args.trace:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
    res = serve(cl, c, mix, args.seed, args.seconds, bool(args.trace),
                trace_dir, counters=declared_counters(readers.values()))
    times["ramp_s"] = float(mix["ramp_s"])
    run = make_run(res, c, mix, cell, args.seconds, dev.device_kind)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(
                  (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devices[:int(cell["chips"])])}
    breakdown = None
    if args.trace:
        from bench import trace as tr_mod
        path = tr_mod.find_xplane(str(trace_dir))
        if path is None:
            log("the profiler wrote no trace")
            return 4
        run.trace = tr_mod.load(path)
        lo, hi = run.trace.window
        device["busy_s"] = tr_mod.busy_s(run.trace, lo, hi)
        device["window_s"] = hi - lo
        breakdown = {
            "device_ops": tr_mod.top_ops(
                [s for s in tr_mod.all_ops(run.trace) if lo <= s[1] < hi]),
            "idle_gaps": tr_mod.idle_gaps(run.trace, lo, hi)}

    metrics = {}
    for m in wanted:
        v = readers[m["name"]].read(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    endless = [n for n, v in metrics.items() if not np.isfinite(v["value"])]
    if endless:
        # a tail that lands on a round that never finished is infinite: the
        # run has no number to report, and prints no result
        log(f"no finite value for {endless}: rounds due in the window were "
            f"still unfinished when the drain ended")
        return 5

    rec = res["recorder"]
    lateness = sorted(res["loop"].arrival_lateness) or [0.0]
    attempted = len(run.rounds)
    failed = sum(1 for r in run.rounds
                 if r.first is None or len(r.times) < r.output)
    log(f"setup: weights {times['weights_s']:.3f}s build {times['build_s']:.3f}s "
        f"profile {times['profile_s']:.3f}s warm-up {times['warm_up_s']:.3f}s "
        f"ramp {times['ramp_s']:.3f}s total {run.setup_s:.3f}s; warm-up "
        f"shapes {warm}")
    log(f"samples: sessions {len(res['plans'])} rounds due in window "
        f"{attempted} unfinished {failed} engine calls {len(rec.calls)} "
        f"compiles in window "
        f"{sum(1 for x in run.compile_times if run.w0 <= x < run.w1)}")
    steps = [k for k in rec.calls
             if k.origin == "decode_once" and run.w0 <= k.t0 < run.w1]
    if steps:
        slots = c["serving"]["decode_slots"]
        cap = slots * c["serving"]["max_len"]
        log(f"decode batch in window: {len(steps)} steps, mean rows "
            f"{sum(len(k.segments) for k in steps) / len(steps):.3f} of "
            f"{slots} slots, mean tokens held "
            f"{sum(sum(x for _, x in k.segments) for k in steps) / len(steps):.1f}"
            f" of {cap}")
    waits = sorted(r.first - r.due for r in run.rounds if r.first is not None)
    log(f"ttft_s of the rounds due in the window that had a first token: "
        f"{[round(w, 6) for w in waits]}")
    log(f"generator lateness: p50 {percentile(lateness, 0.5):.6f}s "
        f"p95 {percentile(lateness, 0.95):.6f}s max {lateness[-1]:.6f}s")

    # free the program's state before the reference runs
    served = served_sequences(res["plans"], rec)
    res["loop"]._heap.clear()
    del cl, run, res, rec
    gc.collect()
    held = jax.live_arrays()
    log(f"device bytes still held before the reference: "
        f"{sum(x.nbytes for x in held)} in {len(held)} arrays")

    result = {"correct": True, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    from bench import check
    verdict = check.compare(c, mix, args.seed, served, search)
    result["correct"] = verdict["correct"]
    result["check"] = verdict["numbers"]
    log(f"gap readings: {verdict.get('readings')}, widest "
        f"{verdict.get('widest')} over "
        f"{verdict.get('checked_tokens')} served tokens of "
        f"{verdict.get('checked_sessions')} sessions, "
        f"{verdict.get('checked_local_rounds')} rounds prefilled locally")
    for name, v in verdict["numbers"].items():
        log(f"check {name}: {v['value']!r} limit {v['limit']!r}")
    print(json.dumps(result))
    return 0


def make_run(res, c, mix, cell, seconds, device_kind) -> Run:
    return Run(config=c, mix=mix, cell=cell, seconds=seconds,
               w0=res["w0"], w1=res["w1"], drain_end=res["drain_end"],
               device_kind=device_kind, setup_s=res["setup_s"],
               rounds=[res["records"][k] for k in res["in_window"]],
               all_rounds=list(res["records"].values()),
               recorder=res["recorder"], counters=res["counters"],
               compile_times=res["compiles"],
               trace_window=res["trace_window"])


def served_sequences(plans, rec) -> List[dict]:
    """Per session, the tokens the model saw and the tokens it served, for
    the rounds that finished: round k contributes its prompt, then its
    first token and every decode-step input after it.  ``local_rounds``
    counts the rounds whose prefill ran in the decode worker's packed
    step."""
    out = []
    for p in plans:
        seq, positions, served, local = [], [], [], 0
        for k, r in enumerate(p.rounds):
            lg = rec.rounds.get((p.index, k))
            if lg is None or lg.first is None or len(lg.tokens) < r.output - 1:
                break
            local += (p.index, k) in rec.local_rounds
            toks = [lg.first[1]] + [t for _, t in lg.tokens]
            seq.extend(int(x) for x in p.prompts[k])
            for j, tok in enumerate(toks):
                positions.append(len(seq) - 1)
                served.append(tok)
                if j < len(toks) - 1:
                    seq.append(tok)
        if served:
            out.append({"session": p.index, "tokens": np.asarray(seq, np.int32),
                        "positions": np.asarray(positions, np.int32),
                        "served": np.asarray(served, np.int32),
                        "local_rounds": local})
    return out
