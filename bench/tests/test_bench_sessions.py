"""The traffic generator: every seed serves the same work on the same
schedule, with prompt tokens of its own."""
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2])]

import json  # noqa: E402

import pytest  # noqa: E402

from bench import sessions  # noqa: E402

MIXES = Path(__file__).resolve().parents[1] / "traffic"


def _mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


@pytest.mark.parametrize("name", sorted(p.stem for p in MIXES.glob("*.json")))
def test_seeds_share_the_set_of_shapes_and_gaps(name):
    mix = _mix(name)
    a = sessions.plan(mix, 40, 1, 1000, 8192)
    b = sessions.plan(mix, 40, 2 ** 33 + 5, 1000, 8192)
    key = lambda p: [(r.prompt, r.output, r.env_delay) for r in p.rounds]  # noqa: E731
    assert [key(p) for p in a] == [key(p) for p in b]
    assert [p.arrival for p in a] == [p.arrival for p in b]
    assert any((x != y).any() for p, q in zip(a, b)                 # tokens differ
               for x, y in zip(p.prompts, q.prompts))
    span = mix["ramp_s"] + 40
    rate = mix["arrivals"]["rate_per_s"]
    assert len(a) == round(rate * span)


@pytest.mark.parametrize("name", sorted(p.stem for p in MIXES.glob("*.json")))
def test_lengths_keep_to_granule_and_cache(name):
    mix = _mix(name)
    g = mix["sessions"]["prompt_granule"]
    for p in sessions.plan(mix, 40, 3, 1000, 8192):
        assert all(r.prompt % g == 0 and r.output >= 4 for r in p.rounds)
        assert p.context <= 8192
        assert all(len(t) == r.prompt for t, r in zip(p.prompts, p.rounds))
        assert p.rounds[-1].env_delay == 0.0


def test_rounds_mean_follows_the_censored_geometric():
    import random
    s = {"rounds_mean": 3.96, "rounds_cap": 64}
    rng = random.Random(0)
    n = [sessions._num_rounds(rng, s) for _ in range(40000)]
    assert abs(sum(n) / len(n) - 3.96) < 0.06
