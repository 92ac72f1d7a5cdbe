"""The comparison catches a broken timed path: each fault that a served
cell can have is planted under a whole run, which must report
``correct: false``."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)]

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from repro.serving.engine import Engine  # noqa: E402
from repro.serving.workers import LiveDecodeWorker  # noqa: E402
from test_bench_harness import run_main  # noqa: E402


def _state_unchanged(monkeypatch):
    """The decode step returns the cache it was given, unwritten."""
    real = Engine.decode_step

    def step(self, cache, tokens):
        import jax
        _, logits = real(self, jax.tree.map(jnp.copy, cache), tokens)
        return cache, logits
    monkeypatch.setattr(Engine, "decode_step", step)


def _half_batch(monkeypatch):
    """The decode step leaves the later half of its occupied rows out."""
    real = Engine.decode_step

    def step(self, cache, tokens):
        t = np.asarray(tokens).copy()
        rows = np.flatnonzero(t[:, 0] >= 0)
        t[rows[len(rows) // 2:], 0] = -1
        return real(self, cache, jnp.asarray(t))
    monkeypatch.setattr(Engine, "decode_step", step)


def _token_altered(monkeypatch):
    """A served token is changed where the decode step produces it (every
    third step, in its first occupied slot)."""
    real = LiveDecodeWorker.decode_once
    seen = {"n": 0}

    def once(self):
        dt, toks = real(self)
        seen["n"] += 1
        if toks and seen["n"] % 3 == 0:
            slot = sorted(toks)[0]
            toks[slot] = (toks[slot] + 1) % self.engine.cfg.vocab_size
        return dt, toks
    monkeypatch.setattr(LiveDecodeWorker, "decode_once", once)


def _kv_move_dropped(monkeypatch):
    """The KV moved between the workers (a prefilled increment into its
    decode slot, a history read into the prefill cache) arrives as zeros:
    the exchange left out."""
    import jax

    import repro.serving.workers as workers

    def moved(tree, device):
        return jax.tree.map(
            lambda x: (jax.device_put(jnp.zeros_like(x), device)
                       if jnp.issubdtype(x.dtype, jnp.floating) else x),
            tree)
    monkeypatch.setattr(workers, "reshard", moved)


@pytest.mark.parametrize("cell", [("tiny-decoder", "tiny-agent"),
                                  ("tiny-moe", "tiny-agent-mean")],
                         ids=["widest-gap", "mean-gap"])
@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered, _kv_move_dropped])
def test_fault_makes_the_run_incorrect(fault, cell, monkeypatch, tmp_path):
    """Under either number a mix can compare: the widest gap (the dense
    cell's) and the mean gap (the mixture-of-experts cell's)."""
    fault(monkeypatch)
    config, traffic = cell
    rc, res = run_main(tmp_path, seed=11, config=config, traffic=traffic)
    assert rc == 0
    assert res["correct"] is False
    (name, v), = res["check"].items()
    assert name == ("logit_gap_max" if traffic == "tiny-agent"
                    else "logit_gap_mean")
    assert v["value"] > v["limit"]
