"""What a configuration brings is found by name.

A fixture architecture of another layout (``fixtures/configs/windowed.py``:
alternating sliding-window and full layers, two stacked groups and an
unstacked trailing layer) runs through the harness and its check with its
own layout, work count and reference; a fixture counter and a fixture
span reach fixture readers; the counter readers read the program's
counters.  None of it names a file of the harness.
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import contextlib  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402

import pytest  # noqa: E402

from bench import harness, model, work  # noqa: E402

FIX = Path(__file__).resolve().parent / "fixtures"
SEARCH = (FIX, harness.BENCH_DIR)
COUNTER_READERS = ["queue_wait_ms", "prefill_lag_ms", "decode_lag_ms",
                   "decode_host_ms", "kv_ms_per_round"]
FIXTURE_READERS = ["decode_ctx_tokens", "decode_step_span_ms"]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced run of the windowed fixture: its per-layer metrics are
    the counter readers, the model step's and the fixture's."""
    bm = {"workloads": [{"name": "fixture.windowed", "config": "tiny-windowed",
                         "traffic": "tiny-agent", "chips": 1}],
          "end_to_end": [],
          "per_layer": [{"name": n, "unit": "x"}
                        for n in COUNTER_READERS + FIXTURE_READERS + ["mfu"]]}
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = harness.main(["--workload", "fixture.windowed", "--seed",
                           str(2 ** 33 + 21), "--seconds", "3", "--trace", "1"],
                          require_tpu=False, search=SEARCH, benchmark=bm,
                          out_dir=tmp_path_factory.mktemp("run"), cache=False)
    return rc, json.loads(out.getvalue().strip().splitlines()[-1])


def test_another_layout_runs_and_checks(traced):
    rc, res = traced
    assert rc == 0
    assert res["correct"] is True and res["failed"] == 0
    v = res["check"]["logit_gap_max"]
    assert v["value"] <= v["limit"]


def test_another_layout_has_its_own_tree():
    c = harness.load_config("tiny-windowed", SEARCH)
    groups = {g.path: g for g in model.layout(c)}
    assert groups["stacked/0"].layers == (0, 2)
    assert groups["stacked/1"].layers == (1, 3)
    assert groups["rest/0"].layers == (4,) and not groups["rest/0"].stacked
    tree = model.make_params(c, 3)
    assert tree["stacked"]["1"]["wq"].shape == (2, 64, 4, 16)
    assert tree["rest"]["0"]["wq"].shape == (64, 4, 16)


def test_another_layout_counts_its_own_work():
    """A sliding layer (window 32) sees at most 32 keys per query: 2 new
    tokens after 40 cached see 32 keys each and read 31 + 2 of them; a
    full layer sees 41 and 42 and reads all 42."""
    c = harness.load_config("tiny-windowed", SEARCH)
    H, G, hd = 4, 2, 16
    q_out = 2 * 2 * H * hd
    assert work.attention_work(c, [(2, 40)], 0) == (
        4.0 * H * hd * 64, 2.0 * (q_out + 2 * 33 * G * hd))
    assert work.attention_work(c, [(2, 40)], 1) == (
        4.0 * H * hd * 83, 2.0 * (q_out + 2 * 42 * G * hd))
    per_layer = 64 * 8 * 16 + 4 * 16 * 64 + 3 * 64 * 128
    assert work.step_flops(c, [(2, 40)], 1) == (
        2.0 * 2 * per_layer * 5 + 4.0 * H * hd * (64 * 3 + 83 * 2)
        + 2.0 * 64 * 512)


def test_fixture_counter_reaches_its_reader(traced):
    _, res = traced
    assert res["metrics"]["decode_ctx_tokens"]["value"] > 0


def test_fixture_span_reaches_its_reader(traced):
    _, res = traced
    assert res["metrics"]["decode_step_span_ms"]["value"] > 0


@pytest.mark.parametrize("name", COUNTER_READERS)
def test_counter_reader_reads_the_program(traced, name):
    _, res = traced
    assert res["metrics"][name]["value"] > 0


def test_missing_counter_reads_zero():
    class W:
        steps = 3

    class Cluster:
        coordinator = W()
        prefill_workers = [W(), W()]
        decode_workers = [W()]

    got = harness._counters(Cluster(), ["prefill.steps", "decode.no_such",
                                        "coordinator.steps"])
    assert got == {"prefill.steps": 6, "decode.no_such": 0,
                   "coordinator.steps": 3}
