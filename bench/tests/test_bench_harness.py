"""One cell end to end through LiveCluster on the CPU at a tiny size, on the
wall-clock loop: the result line, the due times, discovery by name, and
the control that the comparison has to reject."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import contextlib  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402

from bench import check, harness, sessions  # noqa: E402

FIX = Path(__file__).resolve().parent / "fixtures"
SEARCH = (FIX, harness.BENCH_DIR)
E2E = [("ttft_p95_s", "s"), ("itl_p95_ms", "ms"), ("slo_attainment", "fraction"),
       ("out_tok_s", "tokens/s"), ("setup_s", "s"), ("rounds_due", "count")]


def fixture_benchmark(config="tiny-decoder", traffic="tiny-agent"):
    """A benchmark whose cell, mix and one metric exist only as fixture
    files: the harness must find them by name."""
    return {
        "workloads": [{"name": "fixture.agent", "config": config,
                       "traffic": traffic, "chips": 1}],
        "end_to_end": [{"name": n, "unit": u} for n, u in E2E],
        "per_layer": [],
    }


def run_main(tmp_path, seed=123, config="tiny-decoder", traffic="tiny-agent"):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = harness.main(["--workload", "fixture.agent", "--seed", str(seed),
                           "--seconds", "3", "--trace", "0"],
                          require_tpu=False, search=SEARCH,
                          benchmark=fixture_benchmark(config, traffic),
                          out_dir=tmp_path, cache=False)
    lines = out.getvalue().strip().splitlines()
    return rc, json.loads(lines[-1]) if lines else None


@pytest.fixture(scope="module")
def result(tmp_path_factory):
    return run_main(tmp_path_factory.mktemp("run"), seed=2 ** 33 + 9)


def test_result_line_schema(result):
    rc, res = result
    assert rc == 0
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "check"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    for name, unit in E2E:
        assert res["metrics"][name]["unit"] == unit
        assert res["metrics"][name]["value"] > 0
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    v = res["check"]["logit_gap_max"]
    assert v["value"] <= v["limit"]


def test_fixture_metric_found_by_name(result):
    _, res = result
    assert res["metrics"]["rounds_due"]["value"] == res["attempted"]


def test_no_tpu_exits_without_result(tmp_path):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = harness.main(["--workload", "fixture.agent", "--seed", "1",
                           "--seconds", "3"], search=SEARCH,
                          benchmark=fixture_benchmark(), out_dir=tmp_path,
                          cache=False)
    assert rc != 0 and out.getvalue() == ""


@pytest.fixture(scope="module", params=[("tiny-decoder", "tiny-agent"),
                                        ("tiny-moe", "tiny-agent-mean")],
                ids=["widest-gap", "mean-gap"])
def served(request):
    config, traffic = request.param
    c = harness.load_config(config, SEARCH)
    mix = json.loads(harness.find("traffic", traffic, ".json",
                                  SEARCH).read_text())
    cl, _ = harness.build(c, mix, 5, jax.devices()[0])
    harness.warm_up(cl, sessions.shape_set(mix, 3, c["serving"]["max_len"]))
    res = harness.serve(cl, c, mix, 5, 3.0, finish=True)
    return c, mix, res


def test_rounds_are_timed_from_when_they_were_due(served):
    c, mix, res = served
    recs = res["records"]
    plans = {p.index: p for p in res["plans"]}
    later = [(k, r) for k, r in recs.items() if k[1] > 0]
    assert later, "the fixture mix has multi-round sessions"
    for (sid, k), r in later:
        prev = recs[(sid, k - 1)]
        assert r.due == prev.times[-1] + plans[sid].rounds[k - 1].env_delay
    for p in res["plans"]:
        assert recs[(p.index, 0)].due == p.arrival
    run = harness.make_run(res, c, mix, {}, 3.0, "cpu")
    ttft = harness.load_module(harness.find("metrics", "ttft_p95_s", ".py",
                                            SEARCH))
    waits = [r.first - r.due for r in run.rounds]
    assert ttft.read(run) == harness.percentile(waits, 0.95)
    assert not res["compiles"] or all(
        not (res["w0"] <= t < res["w1"]) for t in res["compiles"])


def test_control_is_rejected(served):
    """The float8 control reads a wider gap than the limit that the
    program's own tokens keep to, under the number the mix compares."""
    c, mix, res = served
    seqs = harness.served_sequences(res["plans"], res["recorder"])
    v = check.compare(c, mix, 5, seqs, SEARCH, control=True)
    name, limit = mix["check"]["number"], mix["check"]["limit"]
    assert v["correct"] and v["readings"][name] <= limit
    assert v["control"][name] > limit
    assert v["control_correct"] is False
    assert v["control_nonfinite"] == 0


def test_unfinished_round_is_infinite():
    """A round with no first token by the drain's end waits forever: a p95
    that lands on one is infinite, one that does not stays finite."""
    ttft = harness.load_module(harness.find("metrics", "ttft_p95_s", ".py",
                                            SEARCH))

    def run_of(unfinished):
        rounds = [harness.RoundRecord(i, 0, 0.0, 4, first=0.1 * (i + 1))
                  for i in range(40 - unfinished)]
        rounds += [harness.RoundRecord(100 + i, 0, 0.0, 4)
                   for i in range(unfinished)]
        return harness.Run(config={}, mix={}, cell={}, seconds=1.0, w0=0.0,
                           w1=1.0, drain_end=9.0, device_kind="cpu",
                           setup_s=0.0, rounds=rounds, all_rounds=rounds,
                           recorder=None, counters={}, compile_times=[])

    assert ttft.read(run_of(1)) == 0.1 * 39
    assert ttft.read(run_of(3)) == float("inf")
