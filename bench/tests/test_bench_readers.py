"""The per-layer readers of the program's counters, each against a hand
count, and each None where its denominator did not move."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402

from bench import harness  # noqa: E402


def _reader(name):
    return harness.metric_readers([name], (harness.BENCH_DIR,))[name]


def _run(counters, rounds=()):
    """A run whose counters moved by ``counters`` over the window [0, 10)."""
    return harness.Run(
        config={}, mix={}, cell={}, seconds=10.0, w0=0.0, w1=10.0,
        drain_end=12.0, device_kind="cpu", setup_s=1.0, rounds=list(rounds),
        all_rounds=list(rounds), recorder=None,
        counters={k: (5.0, 5.0 + v) for k, v in counters.items()},
        compile_times=[])


def _done(n):
    """n rounds of 3 tokens whose last token fell in the window, and one
    that finished after it."""
    rr = [harness.RoundRecord(i, 0, 0.0, 3, first=1.0, times=[1.0, 2.0, 3.0])
          for i in range(n)]
    return rr + [harness.RoundRecord(99, 0, 0.0, 3, first=9.0,
                                     times=[9.0, 10.0, 11.0])]


CASES = {
    # name: (counters moved, rounds, value)
    "queue_wait_ms": ({"prefill.queue_wait_s": 0.6, "decode.queue_wait_s": 0.2,
                       "prefill.launches": 30, "decode.launches": 10}, (),
                      1e3 * 0.8 / 40),
    "prefill_lag_ms": ({"prefill.lag_s": 1.5, "prefill.steps": 30}, (), 50.0),
    "decode_lag_ms": ({"decode.lag_s": 2.0, "decode.steps": 400}, (), 5.0),
    "decode_host_ms": ({"decode.host_s": 1.0, "decode.steps": 400}, (), 2.5),
    "kv_ms_per_round": ({"prefill.kv_s": 1.2, "decode.kv_s": 0.4}, _done(20),
                        1e3 * 1.6 / 20),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_counter_reader_by_hand(name):
    counters, rounds, want = CASES[name]
    r = _reader(name)
    assert sorted(r.COUNTERS) == sorted(counters)
    assert r.read(_run(counters, rounds)) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(CASES))
def test_counter_reader_is_none_without_a_denominator(name):
    counters, _, _ = CASES[name]
    still = {k: (0 if k.endswith(("launches", "steps")) else v)
             for k, v in counters.items()}
    assert _reader(name).read(_run(still)) is None
