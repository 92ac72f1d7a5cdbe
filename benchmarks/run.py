"""Benchmark entrypoint: one function per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--full | --smoke] [--json PATH]``

Prints one ``name,us_per_call,derived`` CSV line per benchmark (plus each
benchmark's own table above it).  Default is the quick profile (~minutes on
one CPU core); --full runs all three paper models over the full rate grid;
--smoke runs the shared tiny-trace profile (``benchmarks.common.SMOKE``,
<2 min) that CI's benchmark-smoke job gates on.  --json writes the summary
(and smoke rows) to PATH for artifact upload.
"""
import argparse
import json
import sys
import time


def _section(title):
    print(f"\n===== {title} " + "=" * max(0, 60 - len(title)))


def _emit_summary(profile, summary, json_path, extra=None):
    """Print the CSV summary and (optionally) write the artifact JSON —
    one shape for both the smoke gate and the full profiles."""
    _section("SUMMARY  name,us_per_call,derived")
    for name, us, derived in summary:
        print(f"{name},{us:.0f},{derived}")
    if json_path:
        doc = {"profile": profile,
               "summary": [{"name": n, "us": round(us), "derived": d}
                           for n, us, d in summary]}
        doc.update(extra or {})
        with open(json_path, "w") as fh:
            json.dump(doc, fh, indent=2, default=str)
        print(f"wrote {json_path}")


def smoke(json_path=None) -> int:
    """Tiny-trace planner/runtime regression gate (CI benchmark-smoke job).

    Returns a process exit code: non-zero when a smoke invariant fails."""
    from benchmarks.common import SMOKE
    summary, tables, failures = [], {}, []
    t_all = time.time()

    def record(name, t0, rows, derived):
        summary.append((name, (time.time() - t0) * 1e6, derived))
        tables[name] = rows

    _section("smoke: Table 1 trace statistics")
    from benchmarks import table1_traces
    t0 = time.time()
    rows = table1_traces.main()
    worst = max(abs(r["rounds"] - r["rounds_paper"]) / r["rounds_paper"]
                for r in rows)
    if worst > 0.25:
        failures.append(f"table1 rounds diverge from paper ({worst:.3f})")
    record("table1_traces", t0, rows, f"max_rel_err={worst:.3f}")

    _section("smoke: Table 2 joint planner (one trace)")
    from benchmarks import table2_planner
    t0 = time.time()
    rows = table2_planner.run(traces=("hotpotqa",),
                              num_sessions=SMOKE["num_sessions"],
                              chunk_grid=SMOKE["chunk_grid"])
    if any("FAILED" in r["ilp_pick"] for r in rows):
        failures.append("table2 planner produced a degenerate deployment")
    if any(not r["chunks"] for r in rows):
        failures.append("joint planner chose no chunk sizes")
    record("table2_planner", t0, rows, rows[0]["ilp_pick"])

    _section("smoke: Fig. 9 chunked vs whole prefill")
    from benchmarks import fig9_chunked
    t0 = time.time()
    rows = fig9_chunked.run(num_sessions=SMOKE["num_sessions"],
                            seeds=SMOKE["seeds"])
    whole = next(r for r in rows if r["arm"] == "interference"
                 and r["scheduler"] == "ampd")
    chunk = next(r for r in rows if r["arm"] == "interference"
                 and r["scheduler"] == "ampd-chunked")
    gain = 1 - chunk["avg_itl_ms"] / whole["avg_itl_ms"]
    if gain < -0.05:
        failures.append(f"chunked ITL regressed vs whole-task ({gain:+.1%})")
    record("fig9_chunked", t0, rows, f"itl_gain={gain:+.1%}")

    _section("smoke: Fig. 11 work stealing + priority preemption")
    from benchmarks import fig11_stealing
    t0 = time.time()
    rows = fig11_stealing.run(num_sessions=SMOKE["num_sessions"],
                              seeds=SMOKE["seeds"])
    on = next(r for r in rows if r["arm"] == "stealing")
    off = next(r for r in rows if r["arm"] == "no-stealing")
    if on["steals"] < 1:
        failures.append("stealing-enabled skewed run recorded no steals")
    for r in (on, off):
        if r["completed"] != r["arrived"]:
            failures.append(
                f"fig11 {r['arm']}: {r['completed']}/{r['arrived']} "
                "sessions completed (work lost)")
    if on["slo"] < off["slo"] - 0.05:
        failures.append(
            f"stealing hurt SLO attainment ({off['slo']:.3f} -> "
            f"{on['slo']:.3f})")
    record("fig11_stealing", t0, rows,
           f"p95_ttft {off['p95_ttft_s']}s->{on['p95_ttft_s']}s "
           f"steals={on['steals']}")

    _section("smoke: Fig. 13 decode-local offload")
    from benchmarks import fig13_offload
    t0 = time.time()
    rows = fig13_offload.run(num_sessions=SMOKE["num_sessions"],
                             seeds=SMOKE["seeds"])
    by = {r["arm"]: r for r in rows}
    off, loc, ship = (by["decode-offload"], by["local-always"],
                      by["ship-always"])
    if off["migrations"] < 1:
        failures.append("offload-enabled saturated run recorded no migrations")
    for r in rows:
        if r["completed"] != r["arrived"]:
            failures.append(
                f"fig13 {r['arm']}: {r['completed']}/{r['arrived']} "
                "sessions completed (work lost)")
    if off["slo"] < loc["slo"]:
        failures.append(
            f"decode-offload lost to local-always "
            f"({off['slo']:.3f} < {loc['slo']:.3f})")
    record("fig13_offload", t0, rows,
           f"slo local={loc['slo']} ship={ship['slo']} offload={off['slo']} "
           f"migrations={off['migrations']}")

    _section("smoke: Fig. 15 global KV pool (cross-session reuse)")
    from benchmarks import fig15_kv_reuse
    t0 = time.time()
    rows = fig15_kv_reuse.run(num_sessions=SMOKE["num_sessions"],
                              seeds=SMOKE["seeds"])
    by = {r["arm"]: r for r in rows}
    pool, priv = by["kv-pool"], by["private"]
    if pool["cache_hits"] < 1 or pool["hit_tokens"] < 1:
        failures.append("kv-pool shared-prefix run recorded no cache hits")
    for r in rows:
        if r["completed"] != r["arrived"]:
            failures.append(
                f"fig15 {r['arm']}: {r['completed']}/{r['arrived']} "
                "sessions completed (work lost)")
    if pool["slo"] < priv["slo"]:
        failures.append(
            f"kv-pool lost to the private-cache baseline "
            f"({pool['slo']:.3f} < {priv['slo']:.3f})")
    record("fig15_kv_reuse", t0, rows,
           f"slo private={priv['slo']} pool={pool['slo']} "
           f"hits={pool['cache_hits']}")

    _section("smoke: Fig. 16 elastic autoscaling over the plan lattice")
    from benchmarks import fig16_autoscale
    t0 = time.time()
    rows = fig16_autoscale.run(num_sessions=SMOKE["num_sessions"],
                               seeds=SMOKE["seeds"])
    by = {r["arm"]: r for r in rows}
    static, auto = by["static-plan"], by["autoscale"]
    for r in rows:
        if r["completed"] != r["arrived"]:
            failures.append(
                f"fig16 {r['arm']}: {r['completed']}/{r['arrived']} "
                "sessions completed (work lost across replan)")
    if auto["replans"] < 1:
        failures.append("fig16 autoscale arm survived a kill + resize "
                        "without recording a replan")
    if auto["slo"] < static["slo"] - 0.05:
        failures.append(
            f"fig16 autoscale lost to the static plan "
            f"({auto['slo']:.3f} < {static['slo']:.3f} - 0.05)")
    record("fig16_autoscale", t0, rows,
           f"slo static={static['slo']} "
           f"scratch={by['replan-scratch']['slo']} auto={auto['slo']} "
           f"replans={auto['replans']}")

    _section("smoke: Fig. 17 per-class prefill pools + tenant SLO classes")
    from benchmarks import fig17_classes
    t0 = time.time()
    rows = fig17_classes.run(num_sessions=SMOKE["num_sessions"],
                             seeds=SMOKE["seeds"])
    by = {r["arm"]: r for r in rows}
    blind, classed = by["class-blind"], by["classed"]
    for r in rows:
        if r["completed"] != r["arrived"]:
            failures.append(
                f"fig17 {r['arm']}: {r['completed']}/{r['arrived']} "
                "sessions completed (work lost)")
    if classed["slo"] < blind["slo"]:
        failures.append(
            f"fig17 classed scheduling lost to class-blind "
            f"({classed['slo']:.3f} < {blind['slo']:.3f})")
    record("fig17_classes", t0, rows,
           f"slo blind={blind['slo']} "
           f"deadlines={by['classed-deadlines']['slo']} "
           f"classed={classed['slo']}")

    _section("smoke: Fig. 12 multi-process transport (measured KV path)")
    from benchmarks import fig12_transport
    t0 = time.time()
    try:
        rows, links = fig12_transport.run(num_sessions=2)
    except Exception as e:  # noqa: BLE001 — spawn failure is a gate failure
        rows, links = [], []
        failures.append(f"fig12 multiprocess transports did not run: {e!r}")
    for kind in ("proc", "tcp"):
        arm = next((r for r in rows if r["transport"] == kind), None)
        if arm is None:
            continue
        if arm["completed"] != arm["arrived"]:
            failures.append(
                f"fig12 {kind} transport lost work "
                f"({arm['completed']}/{arm['arrived']} completed)")
        if not arm["kv_ms"] > 0 or not arm["kv_bytes"] > 0:
            failures.append(
                f"fig12 {kind} transport reported no measured KV transfer "
                f"(kv_ms={arm['kv_ms']}, kv_bytes={arm['kv_bytes']})")
    # §16: the fitted per-link-class t_kv must respect the physical ordering
    # intra-process <= intra-host <= cross-host at a representative payload
    if links:
        by_link = {li["link"]: li for li in links}
        order = ("intra-process", "intra-host", "cross-host")
        # price a representative 8 MiB payload from the RAW Hockney
        # coefficients (the display fields round — a CPU-smoke socket fit
        # can legitimately round to 0.0 GiB/s)
        cost = {k: by_link[k]["alpha_s"] + (8 << 20) * by_link[k]["inv_bw"]
                for k in order}
        for a, b in zip(order, order[1:]):
            if cost[a] > cost[b] + 1e-12:
                failures.append(
                    f"fig12 per-link t_kv fit not monotone: {a}={cost[a]} "
                    f"> {b}={cost[b]}")
    proc = next((r for r in rows if r["transport"] == "proc"), None)
    tcp = next((r for r in rows if r["transport"] == "tcp"), None)
    record("fig12_transport", t0, {"rows": rows, "links": links},
           (f"proc kv={proc['kv_bytes']}B/{proc['kv_ms']}ms "
            f"tcp kv={tcp['kv_bytes']}B/{tcp['kv_ms']}ms"
            if proc and tcp else "unavailable"))

    _section("smoke: Fig. 14 ragged fused megakernel (packed vs dense)")
    from benchmarks import fig14_ragged
    t0 = time.time()
    try:
        rows = fig14_ragged.run(num_sessions=2)
    except Exception as e:  # noqa: BLE001 — either arm failing is a gate fail
        rows = []
        failures.append(f"fig14 ragged fused arms did not run: {e!r}")
    by = {r["arm"]: r for r in rows}
    dense, packed = by.get("dense"), by.get("packed")
    micro = by.get("microbench")
    if dense is not None and packed is not None:
        for r in (dense, packed):
            if r["completed"] != r["arrived"]:
                failures.append(
                    f"fig14 {r['arm']}: {r['completed']}/{r['arrived']} "
                    "sessions completed (work lost)")
        # the packed path must be a pure execution-layer swap: same decisions,
        # same generated tokens, same number of fused steps as dense
        if packed.pop("tokens", None) != dense.pop("tokens", None):
            failures.append("fig14 packed arm generated different tokens "
                            "than the dense arm")
        if packed["fused_steps"] != dense["fused_steps"]:
            failures.append(
                f"fig14 fused-step count diverged (dense "
                f"{dense['fused_steps']}, packed {packed['fused_steps']})")
        if packed["tokens_uploaded"] >= dense["tokens_uploaded"]:
            failures.append(
                f"fig14 packed arm uploaded no fewer tokens than dense "
                f"({packed['tokens_uploaded']} >= "
                f"{dense['tokens_uploaded']})")
    if micro is not None and micro["speedup"] > micro["roofline_bound"]:
        failures.append(
            f"fig14 microbench speedup {micro['speedup']}x exceeds its "
            f"useful-work roofline bound {micro['roofline_bound']}x")
    record("fig14_ragged", t0, rows,
           (f"fused {dense['fused_ms_per_step']}->"
            f"{packed['fused_ms_per_step']} ms/step, "
            f"micro {micro['speedup']}x (bound {micro['roofline_bound']}x)"
            if packed and micro else "unavailable"))

    _section("smoke: Fig. 10 joint vs two-stage planning")
    from benchmarks import fig10_joint_plan
    t0 = time.time()
    rows = fig10_joint_plan.run(num_sessions=SMOKE["num_sessions"] - 4,
                                max_candidates=SMOKE["max_candidates"],
                                chunk_grid=SMOKE["chunk_grid"],
                                degrees=(1, 2, 4))
    two = next(r for r in rows if r["strategy"] == "two-stage")
    joint = next(r for r in rows if r["strategy"] == "joint")
    if joint["slo"] < two["slo"]:
        failures.append(
            f"joint planning lost to two-stage "
            f"({joint['slo']:.3f} < {two['slo']:.3f})")
    record("fig10_joint_plan", t0, rows,
           f"joint={joint['slo']:.3f} two_stage={two['slo']:.3f}")

    _emit_summary("smoke", summary, json_path,
                  extra={"wall_seconds": round(time.time() - t_all, 2),
                         "failures": failures, "tables": tables})
    print(f"smoke wall time: {time.time() - t_all:.1f}s")
    for f in failures:
        print(f"SMOKE FAILURE: {f}")
    return 1 if failures else 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-trace regression gate (<2 min; used by CI)")
    ap.add_argument("--json", default=None,
                    help="write the summary as JSON to this path")
    args = ap.parse_args(sys.argv[1:])
    from benchmarks import common  # noqa: F401  (puts src/ on sys.path)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.smoke:
        sys.exit(smoke(args.json))

    summary = []

    def record(name, t0, derived):
        us = (time.time() - t0) * 1e6
        summary.append((name, us, derived))

    _section("Table 1: trace statistics")
    from benchmarks import table1_traces
    t0 = time.time()
    rows = table1_traces.main()
    worst = max(abs(r["rounds"] - r["rounds_paper"]) / r["rounds_paper"]
                for r in rows)
    record("table1_traces", t0, f"max_rel_err={worst:.3f}")

    _section("Fig. 7: planning time vs cluster size")
    from benchmarks import fig7_planning_time
    t0 = time.time()
    rows = fig7_planning_time.main()
    record("fig7_planning_time", t0,
           f"512gpu={rows[-2]['seconds']}s" if len(rows) > 1 else "")

    _section("Table 2: planner vs simulated serving ranking")
    from benchmarks import table2_planner
    t0 = time.time()
    rows = table2_planner.main()
    record("table2_planner", t0, f"{len(rows)} traces")

    _section("Fig. 4: end-to-end SLO attainment")
    from benchmarks import fig4_e2e
    t0 = time.time()
    rows = fig4_e2e.main(quick=not args.full)
    wins = sum(1 for r in rows if r["ampd_vs_best_base"] >= -0.02)
    record("fig4_e2e", t0, f"ampd_best_or_tied={wins}/{len(rows)}")

    _section("Fig. 5: ablation (routing / reordering)")
    from benchmarks import fig5_ablation
    t0 = time.time()
    rows = fig5_ablation.main()
    full = [r["slo"] for r in rows if r["variant"].startswith("+both")]
    base = [r["slo"] for r in rows if r["variant"].startswith("base")]
    record("fig5_ablation", t0,
           f"ampd_minus_base={sum(full)/len(full)-sum(base)/len(base):+.3f}")

    _section("Fig. 6: sensitivity (w, alpha, beta)")
    from benchmarks import fig6_sensitivity
    t0 = time.time()
    rows = fig6_sensitivity.main()
    record("fig6_sensitivity", t0, f"{len(rows)} points")

    _section("Fig. 8: average end-to-end latency")
    from benchmarks import fig8_latency
    t0 = time.time()
    rows = fig8_latency.main()
    record("fig8_latency", t0, f"{len(rows)} traces")

    _section("Fig. 9: chunked incremental prefill (beyond-paper)")
    from benchmarks import fig9_chunked
    t0 = time.time()
    rows = fig9_chunked.main()
    whole = next(r for r in rows if r["arm"] == "interference"
                 and r["scheduler"] == "ampd")
    chunk = next(r for r in rows if r["arm"] == "interference"
                 and r["scheduler"] == "ampd-chunked")
    record("fig9_chunked", t0,
           f"itl_gain={(1 - chunk['avg_itl_ms'] / whole['avg_itl_ms']):+.1%}")

    _section("Fig. 11: work stealing + priority preemption (beyond-paper)")
    from benchmarks import fig11_stealing
    t0 = time.time()
    rows = fig11_stealing.main()
    off = next(r for r in rows if r["arm"] == "no-stealing")
    on = next(r for r in rows if r["arm"] == "stealing")
    record("fig11_stealing", t0,
           f"p95_ttft_gain={(1 - on['p95_ttft_s'] / off['p95_ttft_s']):+.1%}")

    _section("Fig. 13: adaptive decode-local offload (beyond-paper)")
    from benchmarks import fig13_offload
    t0 = time.time()
    rows = fig13_offload.main()
    by = {r["arm"]: r for r in rows}
    record("fig13_offload", t0,
           f"slo: local={by['local-always']['slo']} "
           f"ship={by['ship-always']['slo']} "
           f"offload={by['decode-offload']['slo']}")

    _section("Fig. 15: global KV pool, cross-session reuse (beyond-paper)")
    from benchmarks import fig15_kv_reuse
    t0 = time.time()
    rows = fig15_kv_reuse.main()
    by = {r["arm"]: r for r in rows}
    record("fig15_kv_reuse", t0,
           f"slo: private={by['private']['slo']} "
           f"blind={by['pool-blind']['slo']} pool={by['kv-pool']['slo']} "
           f"hit_tokens={by['kv-pool']['hit_tokens']}")

    _section("Fig. 16: elastic autoscaling over the plan lattice (beyond-paper)")
    from benchmarks import fig16_autoscale
    t0 = time.time()
    rows = fig16_autoscale.main()
    by = {r["arm"]: r for r in rows}
    record("fig16_autoscale", t0,
           f"slo: static={by['static-plan']['slo']} "
           f"scratch={by['replan-scratch']['slo']} "
           f"auto={by['autoscale']['slo']} "
           f"replans={by['autoscale']['replans']}")

    _section("Fig. 17: per-class prefill pools + tenant SLOs (beyond-paper)")
    from benchmarks import fig17_classes
    t0 = time.time()
    rows = fig17_classes.main()
    by = {r["arm"]: r for r in rows}
    record("fig17_classes", t0,
           f"slo: blind={by['class-blind']['slo']} "
           f"deadlines={by['classed-deadlines']['slo']} "
           f"classed={by['classed']['slo']} "
           f"interactive {by['class-blind']['slo_interactive']}->"
           f"{by['classed']['slo_interactive']}")

    _section("Fig. 12: multi-process transport, measured KV path (beyond-paper)")
    from benchmarks import fig12_transport
    t0 = time.time()
    try:
        rows, _links = fig12_transport.main()
        proc = next(r for r in rows if r["transport"] == "proc")
        record("fig12_transport", t0,
               f"kv={proc['kv_bytes']}B in {proc['kv_ms']}ms "
               f"over {proc['kv_transfers']} transfers")
    except Exception as e:  # noqa: BLE001
        record("fig12_transport", t0, f"skipped ({e})")

    _section("Fig. 14: ragged fused megakernel, packed batching (beyond-paper)")
    from benchmarks import fig14_ragged
    t0 = time.time()
    rows = fig14_ragged.main()
    by = {r["arm"]: r for r in rows}
    record("fig14_ragged", t0,
           f"fused {by['dense']['fused_ms_per_step']}->"
           f"{by['packed']['fused_ms_per_step']} ms/step, "
           f"micro {by['microbench']['speedup']}x")

    _section("Fault tolerance / stragglers (beyond-paper)")
    from benchmarks import fault_tolerance
    t0 = time.time()
    rows = fault_tolerance.main()
    record("fault_tolerance", t0,
           f"recoveries={sum(r['recoveries'] for r in rows)}")

    _section("Kernel micro-bench")
    from benchmarks import kernel_bench
    t0 = time.time()
    kernel_bench.main()
    record("kernel_bench", t0, "ref-path CPU")

    _section("Roofline (from dry-run artifacts)")
    from benchmarks import roofline
    t0 = time.time()
    try:
        rows = roofline.main()
        doms = {}
        for r in rows:
            doms[r["bottleneck"]] = doms.get(r["bottleneck"], 0) + 1
        record("roofline", t0, f"cells={len(rows)} bottlenecks={doms}")
    except Exception as e:  # noqa: BLE001
        record("roofline", t0, f"skipped ({e})")

    _emit_summary("full" if args.full else "quick", summary, args.json)


if __name__ == "__main__":
    main()
