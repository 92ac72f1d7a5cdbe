"""Smoke test of the served path on a TPU: gemma2-2b at its published widths.

Drives ``LiveCluster`` (inproc) -> ``Coordinator`` -> ``Engine`` -> the
Pallas kernels for gemma2-2b (26 layers, d_model 2304, 8 heads with kv 4,
head_dim 256, vocab 256000, bf16; random weights from ``--seed``) and checks
what comes out:

* kernels: ``flash_prefill``, ``decode_attn`` and ``ragged_fused`` against
  their jnp references at gemma2-2b widths and serving block sizes;
* serve: 4 multi-round sessions (3 rounds each) through one prefill and one
  decode worker; every session finishes, every round has a TTFT, the decode
  worker ends with no KV held, the packed fused step ran, and the lowered
  packed and dense steps contain Pallas kernels (``tpu_custom_call``);
* logits: one prompt's last-position logits from the served engine (dense
  flash prefill, and the packed ragged step) against an ``impl="ref"``
  engine on the same params.

``--chips 4`` runs only the placement check: 2 prefill + 2 decode workers,
one per chip, against the same trace with all four workers on chip 0.
Greedy tokens must be identical, each engine's arrays must sit on its own
device only, and KV must move between chips.

Usage (on a TPU host; the script fails without one)::

    python chip_smoke.py [--chips 4] [--seed 0]

Earlier lines report each phase; the last line of stdout is one JSON object
``{"ok": true, "device": {...}}``.  Any failure raises, and the exit code is
non-zero.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.types import SLOSpec  # noqa: E402
from repro.kernels.decode_attn.ops import decode_attention  # noqa: E402
from repro.kernels.flash_prefill.ops import flash_attention  # noqa: E402
from repro.kernels.ragged_fused.ops import (  # noqa: E402
    INVALID_POS,
    PACK_ALIGN_TPU,
    build_pack,
    ragged_attention,
)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.transformer import _attn_scale  # noqa: E402
from repro.serving import (  # noqa: E402
    ClusterSpec,
    Engine,
    LiveCluster,
    make_live_sessions,
)
from repro.serving.engine import chunk_limit  # noqa: E402

MODEL = "gemma2-2b"
MAX_LEN = 2048
MAX_SLOTS = 8
#: 4 sessions x 3 rounds: a 224-token first prompt, 48-token increments,
#: 24 greedy tokens per round (context 392 < MAX_LEN).  224 keeps a local
#: round-0 pack (224 + 3 decode rows x 8) inside the 256-token bucket.
TRACE = dict(num_sessions=4, rounds=3, prefill_len=224, incr_len=48,
             decode_len=24)
#: Kernel outputs are bf16: one rounding at |o| < 4 is at most 2^-6, and
#: the kernels and references both accumulate in f32 from the same bf16
#: inputs, so they may differ by about one output rounding.
KERNEL_TOL = 2e-2
#: Logits: the served and ref engines differ only in the attention
#: implementation, but the 26-layer residual stream is bf16, so each layer
#: may round differently and the logits themselves are bf16 products.  A
#: bound of 2^-4 of the largest reference logit allows a few compounded
#: roundings (bf16 keeps 8 significant bits) and no more.
LOGIT_REL_TOL = 2.0 ** -4
#: Round 0 always goes to the prefill worker (no TTFT limit binds); the
#: negative TTIT leaves the prefill side no slack for increments, so rounds
#: 1-2 run decode-local as packed fused steps.  The routing therefore takes
#: both paths whatever the measured step times are.
MIXED_SLO = SLOSpec(ttft_thres=1e6, itl_thres=1e6, ttit_thres=-1.0)
#: Every prefill remote: each session's numerics depend only on its own
#: tokens (B=1 prefill, fixed (MAX_SLOTS, 1) decode rectangle), so greedy
#: tokens cannot depend on which worker or chip served them.
REMOTE_SLO = SLOSpec(ttft_thres=1e6, itl_thres=1e6)


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileStats:
    """Backend compiles and persistent-cache hits, from JAX's monitoring."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def peak_bytes() -> list:
    """``peak_bytes_in_use`` of each local device: a high-water mark over
    the whole process, so the phase that raised it is the one it grew in."""
    return [d.memory_stats()["peak_bytes_in_use"] for d in jax.local_devices()]


@contextlib.contextmanager
def phase(name: str, stats: CompileStats):
    c0, s0, h0 = stats.count, stats.seconds, stats.cache_hits
    t0 = time.perf_counter()
    yield
    print(f"phase {name}: wall {time.perf_counter() - t0:.2f}s, "
          f"{stats.count - c0} compiles in {stats.seconds - s0:.2f}s, "
          f"{stats.cache_hits - h0} persistent-cache hits, "
          f"peak_bytes_in_use {peak_bytes()}", flush=True)


def max_abs(a, b) -> float:
    return float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                 - jnp.asarray(b, jnp.float32))))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def check_kernels(cfg, seed: int) -> None:
    """Each Pallas kernel vs its jnp reference at gemma2-2b widths."""
    H, G, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    B, S, T = MAX_SLOTS, 256, MAX_LEN
    kw = dict(scale=_attn_scale(cfg), attn_softcap=cfg.attn_logit_softcap)
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    k = jax.random.normal(keys[0], (B, T, G, hd), jnp.bfloat16)
    v = jax.random.normal(keys[1], (B, T, G, hd), jnp.bfloat16)
    # per-row cache lengths, all >= S so every query has keys to attend
    lens = np.linspace(S, T, B).astype(np.int32)
    ar = np.arange(T, dtype=np.int32)[None, :]
    kv_pos = jnp.asarray(np.where(ar < lens[:, None], ar, INVALID_POS))
    errs = {}

    q = jax.random.normal(keys[2], (B, S, H, hd), jnp.bfloat16)
    q_pos = jnp.asarray(lens[:, None] - S + np.arange(S, dtype=np.int32))
    args = dict(q_positions=q_pos, kv_positions=kv_pos, causal=True, **kw)
    errs["flash_prefill"] = max_abs(flash_attention(q, k, v, **args),
                                    flash_attention(q, k, v, force_ref=True,
                                                    **args))

    qd = jax.random.normal(keys[3], (B, 1, H, hd), jnp.bfloat16)
    args = dict(q_positions=jnp.asarray(lens[:, None] - 1),
                kv_positions=kv_pos, **kw)
    errs["decode_attn"] = max_abs(decode_attention(qd, k, v, **args),
                                  decode_attention(qd, k, v, force_ref=True,
                                                   **args))

    # one 200-token chunk on row 0 plus a single decode token on rows 1..7,
    # aligned to the TPU pack block: P = 200 + 7 * 8 = 256
    segs = [(0, np.zeros(200, np.int32), int(lens[0]) - 200)]
    segs += [(r, np.zeros(1, np.int32), int(lens[r]) - 1)
             for r in range(1, B)]
    pack = build_pack(segs, align=PACK_ALIGN_TPU)
    P = pack["total"]
    qp = jax.random.normal(keys[2], (P, H, hd), jnp.bfloat16)
    args = dict(q_rows=jnp.asarray(pack["rows"]),
                q_positions=jnp.asarray(pack["positions"]),
                kv_positions=kv_pos, causal=True,
                block_q=PACK_ALIGN_TPU, **kw)
    errs["ragged_fused"] = max_abs(ragged_attention(qp, k, v, **args),
                                   ragged_attention(qp, k, v, force_ref=True,
                                                    **args))
    for name, e in errs.items():
        print(f"kernel {name}: max |kernel - ref| = {e} (tol {KERNEL_TOL})")
        check(e <= KERNEL_TOL, f"{name} differs from its reference by {e}")


def lowered_has_kernel(fn, *args) -> bool:
    return "tpu_custom_call" in fn.lower(*args).as_text()


def check_served_steps(dec) -> None:
    """The dense and packed steps the decode worker ran hold Pallas calls."""
    eng = dec.engine
    check(bool(eng._packed_fns), "the decode worker never ran a packed step")
    out = {"dense": lowered_has_kernel(
        eng._step, eng.params, dec.cache,
        jnp.zeros((dec.max_slots, 1), jnp.int32))}
    for (p, n_out), fn in sorted(eng._packed_fns.items()):
        z = jnp.zeros((p,), jnp.int32)
        out[f"packed P={p} n_out={n_out}"] = lowered_has_kernel(
            fn, eng.params, dec.cache, z, z, z, jnp.zeros((n_out,), jnp.int32))
    for step, ok in out.items():
        print(f"lowered {step} step: tpu_custom_call "
              f"{'present' if ok else 'MISSING'}")
        check(ok, f"the lowered {step} step holds no Pallas kernel")


def check_logits(prefill_eng, decode_eng, prompt) -> None:
    """Last-position logits of one prompt: served engine vs impl="ref"."""
    prompt = prompt[:chunk_limit(prefill_eng.cfg, prefill_eng.max_len)]
    ref_eng = Engine(prefill_eng.cfg, max_len=prefill_eng.max_len,
                     impl="ref", params=prefill_eng.params,
                     device=prefill_eng.device)
    _, ref = ref_eng.prefill(prompt)
    # calibration, not a check: two XLA attentions that differ only in
    # accumulation order, to read the kernels' differences against
    chunked = Engine(prefill_eng.cfg, max_len=prefill_eng.max_len,
                     impl="chunked", params=prefill_eng.params,
                     device=prefill_eng.device).prefill(prompt)[1]
    print("logits chunked-ref vs ref (calibration): max |diff| = "
          f"{max_abs(chunked, ref)}")
    _, dense = prefill_eng.prefill(prompt)
    _, packed, _ = decode_eng.run_packed(decode_eng.new_cache(1),
                                         [(0, prompt)])
    ref = np.asarray(ref, np.float32)
    ref_top1 = int(np.argmax(ref))
    tol = LOGIT_REL_TOL * float(np.max(np.abs(ref)))
    for name, got in (("dense", dense), ("packed", packed[0])):
        got = np.asarray(got, np.float32)
        check(bool(np.isfinite(got).all()), f"{name} logits not finite")
        err = float(np.max(np.abs(got - ref)))
        top1 = int(np.argmax(got))
        print(f"logits {name} vs ref: max |diff| = {err} (tol {tol}), "
              f"top-1 {top1} vs {ref_top1}")
        check(top1 == ref_top1,
              f"{name} top-1 {top1} != ref top-1 {ref_top1}")
        check(err <= tol, f"{name} logits differ from ref by {err} > {tol}")


def check_finished(cl, sessions) -> None:
    for s in sessions:
        check(s.finish_time is not None,
              f"session {s.session_id} did not finish")
        check(len(s.ttfts) == s.num_rounds,
              f"session {s.session_id}: {len(s.ttfts)} TTFTs for "
              f"{s.num_rounds} rounds")
    for d in cl.decode_workers:
        check(d.mem_tokens == 0,
              f"decode worker {d.idx} ends holding {d.mem_tokens} tokens")
    print(f"all {len(sessions)} sessions finished; every round has a TTFT; "
          "decode workers hold no KV")


def one_chip(cfg, seed: int, stats: CompileStats) -> None:
    with phase("kernels", stats):
        check_kernels(cfg, seed)
    with phase("cluster_build", stats):
        cl = LiveCluster(cfg, spec=ClusterSpec(n_prefill=1, n_decode=1,
                                               max_slots=MAX_SLOTS,
                                               max_len=MAX_LEN),
                         transport="inproc", slo=MIXED_SLO, seed=seed)
    with phase("serve", stats):
        sessions = make_live_sessions(cfg, seed=seed, **TRACE)
        res = cl.run(sessions)
    check_finished(cl, sessions)
    print(f"served: fused_steps {res.fused_steps}, packed {res.packed}, "
          f"local fraction {res.local_fraction}, KV moved "
          f"{res.kv_bytes_moved} bytes, logical {res.logical_time}s")
    check(res.packed and res.fused_steps > 0, "no packed fused step ran")
    with phase("lowered_steps", stats):
        check_served_steps(cl.decode_workers[0])
    with phase("logits", stats):
        check_logits(
            cl.prefill_workers[0].engine, cl.decode_workers[0].engine,
            sessions[0].prompt_tokens[0])


def _engine_devices(w) -> set:
    leaves = jax.tree.leaves(w.engine.params)
    if hasattr(w, "cache"):
        leaves += jax.tree.leaves(w.cache)
    return {d for x in leaves for d in x.devices()}


def serve_placed(cfg, seed: int, devices) -> tuple:
    cl = LiveCluster(cfg, spec=ClusterSpec(n_prefill=2, n_decode=2,
                                           max_slots=MAX_SLOTS,
                                           max_len=MAX_LEN),
                     transport="inproc", slo=REMOTE_SLO, seed=seed,
                     profile=False, devices=devices)
    cl.coordinator.record_decisions = True
    sessions = make_live_sessions(cfg, seed=seed, arrival_gap=0.5, **TRACE)
    res = cl.run(sessions)
    check_finished(cl, sessions)
    workers = cl.prefill_workers + cl.decode_workers
    placement = {f"{w.kind}{w.idx}": str(w.engine.device) for w in workers}
    for w in workers:
        held = _engine_devices(w)
        check(held == {w.engine.device},
              f"{w.kind}{w.idx} on {w.engine.device} holds arrays on {held}")
    used = sorted({f"prefill{wi}" for *_, kind, wi
                   in cl.coordinator.decision_log if kind == "remote"}
                  | {f"decode{s.decode_worker}" for s in sessions})
    tokens = [list(map(int, s.generated)) for s in sessions]
    out = dict(placement=placement, workers_used=used,
               kv_bytes_moved=res.kv_bytes_moved, tokens=tokens)
    del cl, res, sessions, workers
    gc.collect()
    return out


def four_chips(cfg, seed: int, stats: CompileStats) -> None:
    devs = jax.local_devices()
    check(len(devs) >= 4, f"--chips 4 needs 4 local devices, have {len(devs)}")
    with phase("one_chip_placement", stats):
        base = serve_placed(cfg, seed, [devs[0]])
    with phase("four_chip_placement", stats):
        spread = serve_placed(cfg, seed, devs[:4])
    print(f"placement one chip: {base['placement']}")
    print(f"placement four chips: {spread['placement']}")
    print(f"workers used: {spread['workers_used']}; KV moved "
          f"{spread['kv_bytes_moved']} bytes prefill -> decode")
    check(len(set(spread["placement"].values())) == 4,
          "the four workers do not sit on four distinct chips")
    prefill_chips = {v for k, v in spread["placement"].items()
                     if k.startswith("prefill")}
    decode_chips = {v for k, v in spread["placement"].items()
                    if k.startswith("decode")}
    check(not prefill_chips & decode_chips, "prefill and decode share a chip")
    check(spread["kv_bytes_moved"] > 0, "no KV moved between chips")
    same = spread["tokens"] == base["tokens"]
    print(f"greedy tokens identical to the one-chip placement: {same}")
    check(same, "four-chip tokens differ from the one-chip placement")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the one-worker-per-chip placement check")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, inputs and trace")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    stats = CompileStats()
    cfg = get_config(MODEL)
    print(f"device {dev.device_kind} x{len(jax.devices())}; model {cfg.name}: "
          f"{cfg.num_layers}L d={cfg.d_model} H={cfg.num_heads} "
          f"kv={cfg.num_kv_heads} hd={cfg.head_dim} V={cfg.vocab_size} "
          f"{cfg.dtype}; compile cache {cache_dir}", flush=True)
    if args.chips == 4:
        four_chips(cfg, args.seed, stats)
    else:
        one_chip(cfg, args.seed, stats)
    print(f"peak_bytes_in_use: {peak_bytes()}")
    print(f"compiles: {stats.count} in {stats.seconds:.2f}s; "
          f"persistent-cache hits {stats.cache_hits}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
