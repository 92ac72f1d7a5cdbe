"""Readings that set a cell's rate and correctness limit, taken in one
process on the chip.

    python3 bench/calibrate.py --workload <cell> [--sweep 0.1,0.2,0.3]
        [--seeds 1,2,3 --control 3] [--seconds 40] [--seed-seconds 20]
        [--load 0.8] [--write]

``--sweep``: the knee sweep.  The cell's traffic at each rate in turn (each
rate's sessions run to completion before the next), one JSON line per rate
with the end-to-end metrics and the backlog (rounds due in the window that
were unfinished when the drain ended), judged by the SLO limits fixed in
the mix file (derived once on the chip by the rule of
``benchmarks/common.py::slo_for``: the ITL limit 2.2 x the measured
``decode_once``, the TTFT limit 1.5 s x max(1, profiled prefill seconds
per token / 1.6e-4); each line reports ``decode_step_ms`` to check them
by).  The knee is the highest rate, swept upwards until the first that fails, at
which at least 90% of the rounds met both limits with no backlog
(``--knee throughput``: with no backlog alone); the cell's rate is
``--load`` x the knee.

``--seeds``: for each seed, the cell's traffic at its rate for a short
window through the same cluster (weights made anew from that seed), then
the comparison a benchmark run makes; the first ``--control`` seeds also
read the control (the reference in float8).  The limit is set between the
program's widest reading and the control's narrowest (two thirds of the
way up, on a log scale), on the first number that separates them
threefold.

``--write`` stores the rate and the correctness limit in the
cell's mix file (on the machine that ran it; copy them into the repo).
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
from pathlib import Path

if __name__ == "__main__":
    _root = Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(_root), str(_root / "src")]


def say(obj) -> None:
    print(json.dumps(obj), flush=True)


def main(argv=None) -> int:
    import jax

    from bench import check, harness, sessions
    from bench.model import make_params

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sweep", default="")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed-seconds", type=float, default=20.0)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--load", type=float, default=0.8)
    ap.add_argument("--knee", choices=("slo", "throughput"), default="slo")
    ap.add_argument("--write", action="store_true")
    args = ap.parse_args(argv)

    bm = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bm["workloads"]}[args.workload]
    search = (harness.BENCH_DIR,)
    harness.use_compile_cache(jax)
    dev = jax.devices()[0]
    harness.log(f"platform={dev.platform} device_kind={dev.device_kind} "
                f"device_count={len(jax.devices())}")
    if dev.platform != "tpu":
        return 3
    c = harness.load_config(cell["config"], search)
    mix_path = harness.find("traffic", cell["traffic"], ".json", search)
    mix = json.loads(mix_path.read_text())
    seeds = [int(s) for s in args.seeds.split(",") if s]
    rates = sorted(float(r) for r in args.sweep.split(",") if r)
    first = seeds[0] if seeds else 1
    cl, times = harness.build(c, mix, first, dev)
    harness.log(f"profiled: prefill {cl.perf.pre[1]} decode {cl.perf.dec[1]}")
    shapes = sessions.shape_set(
        dict(mix, arrivals={"rate_per_s": max(rates + [
            mix["arrivals"]["rate_per_s"]])}),
        max(args.seconds, args.seed_seconds), c["serving"]["max_len"])
    say({"setup": times, "warm": harness.warm_up(cl, shapes)})

    e2e = [m["name"] for m in bm["end_to_end"] if m["name"] != "setup_s"]
    readers = harness.metric_readers(
        e2e + ["slo_attainment", "decode_step_ms", "local_fraction"], search)
    pw, dw = cl.prefill_workers[0], cl.decode_workers[0]
    sid = [0]

    def serve(m, seed, seconds):
        sid[0] += 1
        res = harness.serve(cl, c, m, seed, seconds,
                            sid_base=sid[0] * 1_000_000, finish=True,
                            counters=harness.declared_counters(
                                readers.values()))
        run = harness.make_run(res, c, m, cell, seconds, dev.device_kind)
        out = {n: r.read(run) for n, r in readers.items()}
        out["unfinished"] = sum(1 for r in run.rounds
                                if r.first is None or len(r.times) < r.output)
        out["rounds"] = len(run.rounds)
        return res, out

    if rates:
        knee = None
        for rate in rates:
            _, out = serve(dict(mix, arrivals={"rate_per_s": rate}), first,
                           args.seconds)
            ok = out["unfinished"] == 0 and (
                args.knee == "throughput"
                or (out.get("slo_attainment") or 0.0) >= 0.9)
            say({"rate": rate, "sustained": ok, **out})
            if not ok:
                break
            knee = rate
        if knee is None:
            say({"knee": None, "note": "the lowest rate swept is not sustained"})
            return 1
        mix["arrivals"]["rate_per_s"] = round(args.load * knee, 4)
        say({"knee": knee, "rate_per_s": mix["arrivals"]["rate_per_s"]})

    lower = {"logit_gap_max": 0.0, "logit_gap_mean": 0.0}
    upper = {"logit_gap_max": math.inf, "logit_gap_mean": math.inf}
    for i, seed in enumerate(seeds):
        if i:
            params = make_params(c, seed, dev)
            pw.engine.params = dw.engine.params = params
            dw.cache = dw.engine.new_cache(dw.max_slots)
            del params
        res, out = serve(mix, seed, args.seed_seconds)
        served = harness.served_sequences(res["plans"], res["recorder"])
        pw.engine.params = dw.engine.params = None
        dw.cache = None
        del res
        gc.collect()
        v = check.compare(c, mix, seed, served, search,
                          control=i < args.control)
        for k in lower:
            lower[k] = max(lower[k], v["readings"][k])
            if v.get("control"):
                upper[k] = min(upper[k], v["control"][k])
        say({"seed": seed, "correct": v["correct"], "readings": v["readings"],
             "widest": v.get("widest"), "control_widest": v.get("control_widest"),
             "checked_tokens": v["checked_tokens"],
             "checked_sessions": v["checked_sessions"],
             "checked_local_rounds": v["checked_local_rounds"],
             "control": v.get("control"),
             "control_nonfinite": v.get("control_nonfinite"),
             "control_correct": v.get("control_correct"), **out})
    if seeds and args.control:
        for k in ("logit_gap_max", "logit_gap_mean"):
            if upper[k] > 0 and upper[k] >= 3 * lower[k]:
                # more room above the lower reading than below the upper
                low = max(lower[k], upper[k] / 9)
                mix["check"].update(number=k, limit=round(
                    low ** (1 / 3) * upper[k] ** (2 / 3), 6))
                break
        say({"lower": lower, "upper": upper, "check": mix["check"]})
    if args.write:
        mix_path.write_text(json.dumps(mix, indent=2) + "\n")
        say({"wrote": str(mix_path), "mix": mix})
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
