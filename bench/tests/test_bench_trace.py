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


def test_idle_gaps_prefer_the_program_span():
    """A program span covering a gap's middle names it (the one that
    started last), before the benchmark's span around the call."""
    tr = _trace()
    tr.spans = [("repro.fused", 4.0, 8.0), ("repro.decode.sample", 5.0, 7.0),
                ("repro.decode.wait", 6.0, 6.5)]
    # gap [4.5, 9] has its middle at 6.75; gap [2.5, 4] at 3.25
    assert trace.idle_gaps(tr, 0.0, 10.0, n=2) == [
        ["repro.decode.sample", pytest.approx(4.5)],
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
    decode = trace.matching(trace.all_ops(tr), r"^%decode_attention\b", lo, hi)
    ragged = trace.matching(trace.all_ops(tr), layers.KERNEL_OP["ragged"], lo, hi)
    # two layers: the prefill runs flash per layer, the decode step the
    # decode kernel, the packed step the ragged kernel
    assert len(flash) == 2 and len(decode) == 2 and len(ragged) == 2
    assert all(trace.time_s(k) > 0 for k in (flash, decode, ragged))
    steps = trace.matching(trace.all_modules(tr), layers.STEP_PROGRAM, lo, hi)
    assert sorted(s[0].split("(")[0] for s in steps) == [
        "jit__pstep", "jit__step", "jit__step"]
    labels = [k for k, _ in trace.top_ops(
        [s for s in trace.all_ops(tr) if lo <= s[1] < hi])]
    assert {"flash_attention", "decode_attention",
            "ragged_attention"} <= set(labels)


def test_kernel_rooflines_on_the_recorded_trace():
    """Each roofline reader takes its own kernel's calls by step kind: the
    decode kernel's reads the dense decode step (four rows holding 41, 2, 1
    and 1 cached tokens) against ``%decode_attention``, the prefill
    kernel's the 64-token prefill against ``%flash_attention``; both
    layers of the tiny decoder run each kernel once per call."""
    from types import SimpleNamespace

    from bench import harness, layers, work
    from bench.probes import Call
    c = {"name": "tiny", "hidden_size": 256, "intermediate_size": 512,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "num_hidden_layers": 2, "vocab_size": 1024, "rms_norm_eps": 1e-5,
         "rope_theta": 1e4, "tie_word_embeddings": False,
         "attention_bias": True, "reference": "decoder"}
    decode = [(1, 41), (1, 2), (1, 1), (1, 1)]
    calls = [Call("execute", "flash", 0.1, 0.2, [(64, 0)]),
             Call("decode_once", "decode", 0.3, 0.4, decode),
             Call("fused_step", "ragged", 0.5, 0.6, [(40, 42), (1, 3)])]
    run = harness.Run(
        config=c, mix={}, cell={}, seconds=1.0, w0=0.0, w1=1.0,
        drain_end=1.0, device_kind="TPU v5 lite", setup_s=0.0, rounds=[],
        all_rounds=[], recorder=SimpleNamespace(calls=calls), counters={},
        compile_times=[], trace=trace.load(str(RECORDED)),
        trace_window=(0.0, 1.0))
    lo, hi = run.trace.window

    def share(op, segs):
        dev = trace.time_s(trace.matching(trace.all_ops(run.trace), op, lo, hi))
        need = 2 * work.roofline_s(*work.attention_work(c, segs), run.device_kind)
        return 100.0 * need / dev

    readers = harness.metric_readers(
        ["decode_attn_roofline", "flash_prefill_roofline"], (harness.BENCH_DIR,))
    got = readers["decode_attn_roofline"].read(run)
    assert got == pytest.approx(share(r"^%decode_attention\b", decode))
    assert 0.0 < got <= 100.0
    got = readers["flash_prefill_roofline"].read(run)
    assert got == pytest.approx(share(layers.KERNEL_OP["flash"], [(64, 0)]))
    assert 0.0 < got <= 100.0
