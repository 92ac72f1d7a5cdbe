"""Trace reduction: busy union, matching, the longest idle gaps, and the
names a trace recorded on a v5e carries (``bench/testdata``)."""
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2])]

import pytest  # noqa: E402

from bench import trace  # noqa: E402


def _trace():
    tr = trace.Trace()
    tr.ops["/device:TPU:0"] = [("fusion.1", 1.0, 2.0), ("flash_kernel", 1.5, 2.5),
                               ("ragged_kernel", 4.0, 4.5), ("copy", 9.0, 11.0)]
    tr.modules["/device:TPU:0"] = [("jit__step", 1.0, 2.5), ("jit__pstep", 4.0, 4.5),
                                   ("jit_dynamic_slice", 6.0, 6.1)]
    tr.host = [("bench:window", 0.0, 10.0), ("bench:prefill", 0.9, 2.6),
               ("bench:decode", 3.9, 4.6), ("bench:decode", 5.0, 8.0)]
    return tr


def test_union_merges_overlaps_and_clips():
    tr = _trace()
    assert tr.window == (0.0, 10.0)
    # [1, 2.5] + [4, 4.5] + [9, 10] (clipped)
    assert trace.busy_s(tr, 0.0, 10.0) == pytest.approx(1.5 + 0.5 + 1.0)


def test_matching_and_time():
    tr = _trace()
    ops = trace.matching(trace.all_ops(tr), r"flash", 0.0, 10.0)
    assert [o[0] for o in ops] == ["flash_kernel"]
    assert trace.time_s(ops) == pytest.approx(1.0)
    progs = trace.matching(trace.all_modules(tr), r"^jit__p?step\b", 0.0, 10.0)
    assert [p[0] for p in progs] == ["jit__step", "jit__pstep"]


def test_idle_gaps_are_named_by_the_host_span():
    tr = _trace()
    gaps = trace.idle_gaps(tr, 0.0, 10.0, n=2)
    # gaps [4.5, 9] (the host was in a decode call), then [2.5, 4] (no span)
    assert gaps == [["bench:decode", pytest.approx(4.5)],
                    ["host", pytest.approx(1.5)]]


RECORDED = Path(__file__).resolve().parents[1] / "testdata" / "engine_steps.xplane.pb"


def test_recorded_v5e_trace():
    """A dense prefill, a dense decode step and a packed step of a tiny
    decoder, recorded on a v5e by ``bench/testdata/record_trace.py``."""
    from bench import layers
    tr = trace.load(str(RECORDED))
    lo, hi = tr.window
    assert 0.0 < trace.busy_s(tr, lo, hi) < hi - lo
    assert [h[0] for h in tr.host] == ["bench:window", "bench:prefill",
                                       "bench:decode", "bench:fused"]
    flash = trace.matching(trace.all_ops(tr), layers.KERNEL_OP["flash"], lo, hi)
    ragged = trace.matching(trace.all_ops(tr), layers.KERNEL_OP["ragged"], lo, hi)
    # two layers: the prefill and the decode step each run flash per layer,
    # the packed step runs the ragged kernel per layer
    assert len(flash) == 4 and len(ragged) == 2
    assert trace.time_s(flash) > 0 and trace.time_s(ragged) > 0
    steps = trace.matching(trace.all_modules(tr), layers.STEP_PROGRAM, lo, hi)
    assert sorted(s[0].split("(")[0] for s in steps) == [
        "jit__pstep", "jit__step", "jit__step"]
    labels = [k for k, _ in trace.top_ops(
        [s for s in trace.all_ops(tr) if lo <= s[1] < hi])]
    assert "flash_attention" in labels and "ragged_attention" in labels
