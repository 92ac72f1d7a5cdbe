"""The program's own spans in a trace recorded on a v5e
(``bench/testdata/record_spans.py``): they sit on the trace's clock beside
the device's programs, so a reduction can name what the host was doing
while the device waited.  The host and device timestamps of such a trace
agree to a millisecond or two, not exactly: here the tiny decode step's
program (50 us) is stamped about 1 ms before the host span that launched
it."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import layers, trace  # noqa: E402

RECORDED = ROOT / "bench" / "testdata" / "engine_steps_spans.xplane.pb"


def _spans():
    """(program spans on the host, step programs on the device), each as
    (name, start_ns, end_ns, stats)."""
    import re

    from jax.profiler import ProfileData
    host, steps = [], []
    for plane in ProfileData.from_file(str(RECORDED)).planes:
        for line in plane.lines:
            for e in line.events:
                sp = (e.name, e.start_ns, e.start_ns + e.duration_ns,
                      dict(e.stats))
                if plane.name.startswith("/host:") and e.name.startswith(
                        "repro."):
                    host.append(sp)
                elif (plane.name.startswith("/device:")
                      and line.name == "XLA Modules"
                      and re.search(layers.STEP_PROGRAM, e.name)):
                    steps.append(sp)
    return host, steps


#: how far apart the host and device stamps of one event may lie
SKEW_NS = 3_000_000


def test_recorded_program_spans():
    host, _ = _spans()
    names = {h[0] for h in host}
    assert {"repro.prefill", "repro.kv.read_history", "repro.kv.extract",
            "repro.join", "repro.kv.attach", "repro.decode.step",
            "repro.decode.wait", "repro.decode.sample",
            "repro.fused"} <= names
    for name, _, _, stats in host:
        if name in ("repro.prefill", "repro.join", "repro.fused"):
            assert {"session", "round"} <= set(stats), (name, stats)
    assert [h[3]["rows"] for h in host if h[0] == "repro.decode.step"] == [4]


def test_decode_wait_meets_the_step_program():
    """Each wait for a decode worker's step lies, within the clocks'
    skew, over the device running a step program: the dense decode step
    over ``jit__step``, the packed local prefill over ``jit__pstep``."""
    host, steps = _spans()
    waits = [h for h in host if h[0] == "repro.decode.wait"]
    assert len(waits) == 2
    for _, lo, hi, _ in waits:
        near = [s for s in steps
                if s[1] < hi + SKEW_NS and lo - SKEW_NS < s[2]]
        assert near, (lo, hi)
    step = [h for h in host if h[0] == "repro.decode.step"][0]
    assert any(s[0].startswith("jit__step") and s[1] < step[2] + SKEW_NS
               and step[1] - SKEW_NS < s[2] for s in steps)


def test_load_keeps_program_spans():
    """``trace.load`` keeps every program span, on the trace's clock in
    seconds, and only those (the benchmark's own stay in ``host``)."""
    from repro.runtime.tracing import SPANS
    host, _ = _spans()
    tr = trace.load(str(RECORDED))
    assert sorted(tr.spans) == sorted((n, s * 1e-9, e * 1e-9)
                                      for n, s, e, _ in host if n in SPANS)
    assert len(tr.spans) == len(host)
    assert all(not n.startswith("repro.") for n, _, _ in tr.host)
