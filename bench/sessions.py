"""The one traffic generator: a mix file's parameters -> timed sessions.

The length arithmetic is the Table-1 generator of the paper's traces
(lognormal prompt and output lengths around their means, a shifted
geometric round count censored at a cap, exponential environment delays),
kept here so that the program cannot move the yardstick.

Every seed serves the same work on the same schedule: the sessions'
shapes and their arrival times come from the mix's fixed ``base_seed``;
the run's seed draws only the prompt token ids (and, in the harness, the
weights).  So two seeds differ in what the tokens say, never in how many
there are or when they are due, and the window of every run holds the same
rounds.
"""
from __future__ import annotations

import math
import random
from dataclasses import dataclass, field
from functools import lru_cache
from typing import List

import numpy as np


@dataclass
class Round:
    prompt: int          # prompt tokens of this round (after the granule)
    output: int          # tokens the user sees: first token + decode steps
    env_delay: float     # seconds the environment takes after this round


@dataclass
class SessionPlan:
    index: int
    arrival: float       # seconds after the traffic starts
    rounds: List[Round]
    prompts: List[np.ndarray] = field(default_factory=list)

    @property
    def context(self) -> int:
        return sum(r.prompt + r.output - 1 for r in self.rounds)


def _lognormal(rng: random.Random, mean: float, sigma: float) -> float:
    mu = math.log(mean) - sigma * sigma / 2.0      # E[X] = mean
    return rng.lognormvariate(mu, sigma)


@lru_cache(maxsize=None)
def _geom_p(mean: float, cap: int) -> float:
    """Success probability of the cap-censored shifted geometric whose mean
    is ``mean`` (bisection on the censored mean)."""
    if mean <= 1.0:
        return 1.0
    lo, hi = 1e-9, 1.0
    for _ in range(100):
        mid = (lo + hi) / 2.0
        if (1.0 - (1.0 - mid) ** cap) / mid > mean:
            lo = mid
        else:
            hi = mid
    return (lo + hi) / 2.0


def _num_rounds(rng: random.Random, s: dict) -> int:
    if s.get("rounds_fixed"):
        return int(s["rounds_fixed"])
    p = _geom_p(float(s["rounds_mean"]), int(s["rounds_cap"]))
    n = 1
    while rng.random() > p and n < s["rounds_cap"]:
        n += 1
    return n


def _shape(rng: random.Random, s: dict, max_len: int) -> List[Round]:
    n = _num_rounds(rng, s)
    boost = float(s.get("first_round_boost", 1.0))
    g = int(s["prompt_granule"])
    rounds, ctx = [], 0
    for r in range(n):
        b = boost if r == 0 else 1.0
        pf = max(8, int(_lognormal(rng, s["prompt_mean"] * b
                                   / (1 + (boost - 1) / n), s["sigma"])))
        pf = -(-pf // g) * g
        out = max(4, int(_lognormal(rng, s["output_mean"], s["sigma"])))
        env = (rng.expovariate(1.0 / s["env_delay_mean_s"])
               if r < n - 1 else 0.0)
        # the session ends at the last round whose context fits the cache
        if ctx + pf + out > max_len:
            break
        ctx += pf + out - 1
        rounds.append(Round(pf, out, env))
    if not rounds:       # a first prompt longer than the cache: cut it
        pf = (max_len - 64) // g * g
        rounds.append(Round(pf, 4, 0.0))
    if rounds:
        rounds[-1].env_delay = 0.0
    return rounds


def plan(mix: dict, seconds: float, seed: int, vocab: int,
         max_len: int) -> List[SessionPlan]:
    """Sessions arriving over ``ramp_s + seconds`` at the mix's fixed rate;
    only the prompt tokens depend on ``seed``."""
    s, rate = mix["sessions"], float(mix["arrivals"]["rate_per_s"])
    span = float(mix["ramp_s"]) + float(seconds)
    n = max(1, int(round(rate * span)))
    base = random.Random(int(s["base_seed"]))
    shapes = [_shape(base, s, max_len) for _ in range(n)]
    gaps = np.array([base.expovariate(rate) for _ in range(n)])
    gaps *= (n / rate) / gaps.sum()          # exactly the offered rate
    rng = np.random.default_rng(int(seed) % (1 << 64))
    t = np.cumsum(gaps) - gaps[0]
    out = []
    for i in range(n):
        sp = SessionPlan(i, float(t[i]), shapes[i])
        sp.prompts = [rng.integers(0, vocab, r.prompt).astype(np.int32)
                      for r in sp.rounds]
        out.append(sp)
    return out


def shape_set(mix: dict, seconds: float, max_len: int) -> List[List[Round]]:
    """The sessions' shapes alone, the same for every seed (for warm-up)."""
    return [p.rounds for p in plan(mix, seconds, 0, 2, max_len)]
