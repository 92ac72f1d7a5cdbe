"""Whether what the timed path served is correct.

After the window closes and the program's state is freed, a sample of the
sessions whose rounds finished (drawn from the seed, and always holding
the longest one) is run through the configuration's plain reference over
the exact token sequence the program saw.  At every position where the
program served a token (greedy), the number compared is the gap by which
the reference's logit of that token lies below the reference's best logit.
Two numbers are read from those gaps, the widest (``logit_gap_max``) and
the mean (``logit_gap_mean``); the mix file names the one compared and its
limit, set from the readings recorded in PERF.md.

``control=True`` also reads the control: the same reference computed with
float8 weights and activations, scored by the gap of the token that it puts
first, and judged by the same number and limit (``control_correct``, which
has to come out false).  Benchmark runs never compute it; the calibration
script does, at the cell's own size.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def sample(served: List[dict], seed: int, tokens: int) -> List[dict]:
    """The longest finished session, the longest of those with a round
    prefilled in the decode worker's packed step (where there is one),
    then others drawn from the seed until about ``tokens`` served tokens
    are covered."""
    if not served:
        return []
    order = sorted(served, key=lambda s: -len(s["tokens"]))
    picked, rest = [order[0]], order[1:]
    local = [s for s in rest if s.get("local_rounds")]
    if local and not order[0].get("local_rounds"):
        picked.append(local[0])
        rest = [s for s in rest if s is not local[0]]
    rng = np.random.default_rng(int(seed) % (1 << 64))
    for i in rng.permutation(len(rest)):
        if sum(len(s["served"]) for s in picked) >= tokens:
            break
        picked.append(rest[i])
    return picked


def _gaps(ref_logits, tokens) -> np.ndarray:
    import jax.numpy as jnp
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, jnp.asarray(tokens)[:, None], -1)[:, 0]
    return np.asarray(best - got)


def compare(c: dict, mix: dict, seed: int, served: List[dict],
            search: Sequence, control: bool = False) -> Dict:
    from bench.model import architecture

    spec = mix["check"]
    picked = sample(served, seed, int(spec["sample_tokens"]))
    if not picked:
        return {"correct": False, "numbers": {
            "served_tokens_checked": {"value": 0, "limit": 1}}}
    ref = architecture(c, search)
    seqs = [s["tokens"] for s in picked]
    pos = [s["positions"] for s in picked]
    logits = ref.logits_at(c, seed, seqs, pos)
    gaps = np.concatenate([_gaps(lg, s["served"])
                           for lg, s in zip(logits, picked)])
    read = _numbers(gaps)
    name, limit = spec["number"], float(spec["limit"])
    out = {"correct": read[name] <= limit,
           "numbers": {name: {"value": read[name], "limit": limit}},
           "readings": read, "widest": _widest(gaps),
           "checked_tokens": int(gaps.size),
           "checked_sessions": len(picked),
           "checked_local_rounds": sum(s.get("local_rounds", 0)
                                       for s in picked)}
    if control:
        # the reference in float8 put in the program's place: its first
        # token at each served position, judged by the same number and limit
        import jax.numpy as jnp
        ctl = ref.logits_at(c, seed, seqs, pos, quant="fp8")
        cg = np.concatenate([_gaps(lg, np.asarray(jnp.argmax(cl, -1)))
                             for lg, cl in zip(logits, ctl)])
        out["control"] = _numbers(cg)
        out["control_nonfinite"] = int(sum(
            np.asarray(jnp.sum(~jnp.isfinite(cl))) for cl in ctl))
        out["control_widest"] = _widest(cg)
        out["control_correct"] = out["control"][name] <= limit
    return out


def _widest(gaps: np.ndarray, n: int = 8) -> List[float]:
    """The ``n`` widest gaps, widest first, for the calibration's log."""
    return [float(g) for g in np.sort(gaps)[::-1][:n]]


def _numbers(gaps: np.ndarray) -> Dict[str, float]:
    return {"logit_gap_max": float(gaps.max()),
            "logit_gap_mean": float(gaps.mean())}
