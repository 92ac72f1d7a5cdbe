"""Live JAX execution engine: one model replica behind jitted step fns.

An ``Engine`` owns params plus three jitted entry points (fresh-cache
prefill, incremental prefill into an existing cache, single-token decode) —
the same builders the dry-run lowers at production scale, here executed for
real.

tp>1 (DESIGN.md §16): an engine can own a tp-way mesh slice — it builds a
``make_worker_mesh(tp)`` mesh and a prefill-mode :class:`ShardingEnv`
(shape-aware logical-axis rules, so decode steps with seq=1 automatically
fall through to context-parallel KV sharding) and traces every step under
``axis_rules``, activating the ``shard()`` annotations in the model code.
Params and fresh caches are placed replicated on the mesh; activation
constraints shard the compute.  When the process has fewer than ``tp``
devices the engine falls back to an unsharded 1x1 layout (the declared
``tp`` is still what the scheduler prices) — worker child processes get
their device count forced by the pool so the fallback never triggers there.

Placement: a tp=1 engine can be given one ``device``; its params and
every cache it creates then live on that device only, and its jitted steps
run there (the cluster places in-process workers one per local device).

``profile_engine`` measures the engine across a small grid of shapes and
fits the AMPD perf-model coefficients (§3 offline profiler): the scheduler
is then driven by *measured* numbers, not analytic constants.  With
``kv=True`` it also times intra-process KV extract/insert round-trips and
fits the ``"intra-process"`` link-class T_kv coefficients (§16); the
socket-borne classes are fitted from ``TransportKVPath`` samples by the
cluster/benchmarks.
"""
from __future__ import annotations

import time
import warnings
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.perf_model import PerfModel
from repro.kernels.ragged_fused.ops import PACK_ALIGN_TPU
from repro.launch.steps import StepOptions
from repro.models import Model, build_model
from repro.models.packed import forward_packed, supports_packed
from repro.models.transformer import forward_cached, init_cache


def _pad_mult(cfg: ModelConfig) -> int:
    m = 8
    if cfg.ssm_state:
        m = max(m, cfg.ssm_chunk)
    return m


def chunk_limit(cfg: ModelConfig, max_len: int) -> int:
    """Largest legal prefill chunk (ring-exactness needs chunk <= window)."""
    lim = max_len
    if cfg.sliding_window:
        lim = min(lim, cfg.sliding_window)
    return lim


class Engine:
    def __init__(self, model_or_cfg, *, max_len: int, key: Optional[jax.Array] = None,
                 params: Optional[Any] = None, opts: Optional[StepOptions] = None,
                 impl: str = "auto", tp: int = 1,
                 device: Optional[jax.Device] = None):
        self.model: Model = (model_or_cfg if isinstance(model_or_cfg, Model)
                             else build_model(model_or_cfg))
        self.cfg = self.model.cfg
        self.max_len = max_len
        self.opts = opts or StepOptions(attn_impl=impl, fsdp=False, remat=False)
        self.pad_mult = _pad_mult(self.cfg)

        #: requested tp degree (what the scheduler prices); mesh_tp is what
        #: this process could actually build (§16)
        self.tp = tp
        self.mesh = None
        self.sharding_env = None
        self.mesh_tp = 1
        if tp > 1:
            if jax.device_count() >= tp:
                from repro.distributed.sharding import ShardingEnv, make_rules
                from repro.launch.mesh import make_worker_mesh
                self.mesh = make_worker_mesh(tp)
                # prefill-mode rules serve both phases: the shape-aware
                # assignment drops seq-sharding for seq=1 decode steps and
                # falls through to kv_seq context parallelism
                self.sharding_env = ShardingEnv(self.mesh,
                                                make_rules(mode="prefill"))
                self.mesh_tp = tp
            else:
                warnings.warn(
                    f"tp={tp} requested but only {jax.device_count()} "
                    f"device(s) visible; engine runs unsharded (scheduler "
                    f"still prices tp={tp})", RuntimeWarning, stacklevel=2)

        #: the device holding this engine's params and caches (None: JAX's
        #: default device, or the tp mesh when sharded)
        self.device = device if self.sharding_env is None else None
        with jax.default_device(self.device):
            self.params = self._place(
                params if params is not None else self.model.init(
                    key if key is not None else jax.random.PRNGKey(0)))

        cfg = self.cfg
        o = self.opts

        def _step(params, cache, tokens, cross_embeds=None, compute_cross=False):
            return forward_cached(cfg, params, cache, tokens,
                                  cross_embeds=cross_embeds,
                                  compute_cross=compute_cross,
                                  impl=o.attn_impl, expert_mode=o.expert_mode)

        self._step = jax.jit(_step, static_argnames=("compute_cross",),
                             donate_argnums=(1,))

        #: token elements shipped host->device by this engine (dense chunk
        #: matrices, packed streams, decode feeds) — the regression metric
        #: for the fused-step upload fix (DESIGN.md §15)
        self.tokens_uploaded = 0
        #: packed-stream segment alignment (kernel q-block on TPU, 1 on CPU)
        self.pack_align = (PACK_ALIGN_TPU
                           if jax.default_backend() == "tpu" else 1)
        #: explicit jit caches keyed on shape buckets: packed steps by
        #: (P_bucket, n_out_bucket), dense fused composers by (batch, width)
        self._packed_fns: Dict[Tuple[int, int], Any] = {}
        self._compose_fns: Dict[Tuple[int, int], Any] = {}

    # ------------------------------------------------------------------
    def _replicated(self):
        from jax.sharding import NamedSharding, PartitionSpec
        return NamedSharding(self.mesh, PartitionSpec())

    def _env(self):
        """Context activating this engine's sharding rules for a step call
        (a no-op ``axis_rules(None)`` for unsharded engines)."""
        from repro.distributed.sharding import axis_rules
        return axis_rules(self.sharding_env)

    def _place(self, tree):
        """Commit a tree to this engine's placement: replicated on its tp
        mesh, or on its one device (created there, so nothing moves)."""
        if self.sharding_env is not None:
            return jax.device_put(tree, self._replicated())
        if self.device is not None:
            return jax.device_put(tree, self.device)
        return tree

    def new_cache(self, batch: int):
        with jax.default_device(self.device):
            return self._place(init_cache(self.cfg, batch, self.max_len))

    def pad_chunk(self, tokens: np.ndarray, batch: int = 1) -> jnp.ndarray:
        """Right-pad a token chunk to the engine's padding multiple."""
        n = len(tokens)
        m = self.pad_mult
        padded = -np.ones((batch, ((n + m - 1) // m) * m), np.int32)
        padded[0, :n] = tokens
        return jnp.asarray(padded)

    def run_chunk(self, cache, tokens: jnp.ndarray,
                  cross_embeds=None, compute_cross: bool = False):
        """Execute one (possibly padded) chunk; returns (cache, logits, aux)."""
        with self._env():
            return self._step(self.params, cache, tokens, cross_embeds,
                              compute_cross=compute_cross)

    def prefill(self, token_ids: np.ndarray, *, cross_embeds=None):
        """Fresh single-request prefill; chunks per window constraints.

        Returns (cache(batch=1), last_logits (V,)).
        """
        cache = self.new_cache(1)
        lim = chunk_limit(self.cfg, self.max_len)
        logits = None
        first = True
        for lo in range(0, len(token_ids), lim):
            chunk = self.pad_chunk(token_ids[lo:lo + lim])
            cache, logits, _ = self.run_chunk(
                cache, chunk,
                cross_embeds=cross_embeds if first else None,
                compute_cross=first and cross_embeds is not None)
            first = False
        return cache, logits[0]

    def decode_step(self, cache, tokens: jnp.ndarray):
        """tokens (B, 1) with -1 marking empty slots; returns (cache, logits)."""
        cache, logits, _ = self.run_chunk(cache, tokens)
        return cache, logits

    # ------------------------------------------------------------------
    # Packed (ragged) fused path — DESIGN.md §15
    # ------------------------------------------------------------------
    @property
    def supports_packed(self) -> bool:
        """Whether this config has a ragged attention pack (pure ATTN/LOCAL
        stacks; recurrent/cross layers fall back to the dense path)."""
        return supports_packed(self.cfg)

    def packed_bucket(self, n: int) -> int:
        """Round a packed length up to a geometric shape bucket so the
        ``run_packed`` jit cache holds O(log max_len) entries, not one per
        distinct pack."""
        b = max(self.pack_align, 8)
        while b < n:
            b *= 2
        return b

    @staticmethod
    def out_bucket(n: int) -> int:
        return ((n + 3) // 4) * 4

    def _packed_fn(self, p_bucket: int, n_out: int):
        key = (p_bucket, n_out)
        fn = self._packed_fns.get(key)
        if fn is None:
            cfg, o = self.cfg, self.opts

            def _pstep(params, cache, tokens, rows, offs, out_idx):
                return forward_packed(cfg, params, cache, tokens, rows, offs,
                                      out_idx, impl=o.attn_impl,
                                      expert_mode=o.expert_mode)

            fn = jax.jit(_pstep, donate_argnums=(1,))
            self._packed_fns[key] = fn
        return fn

    def run_packed(self, cache, segments: List[Tuple[int, np.ndarray]]):
        """Execute one packed fused step: ``segments`` is a list of
        ``(cache_row, tokens)`` — typically one wide prefill chunk plus N
        single-token decode segments sharing the launch.

        Returns (cache, seg_logits (len(segments), V), aux) where row i of
        ``seg_logits`` is the next-token logits of segment i's last token.
        """
        from repro.kernels.ragged_fused.ops import build_pack

        assert self.supports_packed, \
            f"no ragged pack for {self.cfg.layer_pattern}"
        assert segments, "empty pack"
        rows = [r for r, _ in segments]
        assert len(set(rows)) == len(rows), f"duplicate cache rows: {rows}"
        lim = chunk_limit(self.cfg, self.max_len)
        assert all(1 <= len(t) <= lim for _, t in segments), \
            "segment exceeds chunk limit (ring exactness)"

        pack = build_pack([(r, np.asarray(t, np.int32), 0)
                           for r, t in segments], align=self.pack_align)
        P = self.packed_bucket(pack["total"])
        n_out = self.out_bucket(len(segments))
        tokens = np.full((P,), -1, np.int32)
        prows = np.full((P,), -1, np.int32)
        offs = np.zeros((P,), np.int32)
        out_idx = np.zeros((n_out,), np.int32)
        t = pack["total"]
        tokens[:t] = pack["tokens"]
        prows[:t] = pack["rows"]
        offs[:t] = pack["offsets"]
        out_idx[:len(segments)] = pack["last_idx"]
        self.tokens_uploaded += P

        fn = self._packed_fn(P, n_out)
        with self._env():
            cache, logits, aux = fn(self.params, cache, jnp.asarray(tokens),
                                    jnp.asarray(prows), jnp.asarray(offs),
                                    jnp.asarray(out_idx))
        return cache, logits[:len(segments)], aux

    # ------------------------------------------------------------------
    # Dense fused-step composer (the packed=False fallback's upload fix)
    # ------------------------------------------------------------------
    def compose_fused_chunk(self, row_tokens: np.ndarray, slot: int,
                            feed: np.ndarray) -> jnp.ndarray:
        """Build the dense (B, width) fused-step matrix ON DEVICE from the
        compact uploads: the prefill row (width,) and the decode feed (B,)
        (-1 = non-advancing).  Sub-chunks after the first ship feed = all
        ``-1`` so non-advancing rows are masked without re-uploading the
        ``max_slots x width`` rectangle."""
        B, W = len(feed), len(row_tokens)
        key = (B, W)
        fn = self._compose_fns.get(key)
        if fn is None:
            def _compose(row, slot_, feed_):
                ridx = jnp.arange(B, dtype=jnp.int32)
                base = jnp.where(ridx[:, None] == slot_,
                                 jnp.broadcast_to(row[None, :], (B, W)), -1)
                col0 = jnp.where(ridx == slot_, base[:, 0], feed_)
                return base.at[:, 0].set(col0)

            fn = jax.jit(_compose)
            self._compose_fns[key] = fn
        self.tokens_uploaded += W + B
        return fn(jnp.asarray(row_tokens, jnp.int32), jnp.int32(slot),
                  jnp.asarray(feed, jnp.int32))


# ---------------------------------------------------------------------------
# Offline profiler (§3): fit PerfModel coefficients from this engine
# ---------------------------------------------------------------------------

def _time_call(fn, *args, repeats: int = 2, **kw) -> Tuple[float, Any]:
    out = fn(*args, **kw)   # compile + warm
    jax.block_until_ready(jax.tree.leaves(out)[0])
    ts = []
    result = out
    for _ in range(repeats):
        t0 = time.perf_counter()
        result = fn(*args, **kw)
        jax.block_until_ready(jax.tree.leaves(result)[0])
        ts.append(time.perf_counter() - t0)
    return min(ts), result


def profile_engine(engine: Engine, perf: PerfModel, tp: int,
                   *, prefill_lens: Tuple[int, ...] = (32, 64, 128),
                   hist_lens: Tuple[int, ...] = (0, 64),
                   batches: Tuple[int, ...] = (1, 4, 8),
                   fused: bool = False,
                   packed: bool = False,
                   kv: bool = False,
                   kv_lens: Tuple[int, ...] = (16, 48, 96),
                   seed: int = 0) -> PerfModel:
    """Measure the live engine and overwrite perf coefficients for `tp`.

    With ``fused=True`` also measures Sarathi-style fused chunk+decode steps
    (one row prefilling a chunk while ``b`` rows each decode one token) and
    fits the T_fused family (``fit_fused``) — otherwise T_fused re-derives
    from the fitted prefill/decode coefficients.  ``packed=True`` measures
    the fused samples on the ragged packed step (``run_packed``) instead of
    the dense rectangle, so the fitted T_fused absorbs the megakernel
    speedup and the tuner/planner/offload guard inherit it.

    ``kv=True`` (§16) additionally times ``extract_range``+``insert_range``
    round-trips — the in-process KV move the inproc transport performs on a
    remote placement — and fits the ``"intra-process"`` link-class T_kv
    coefficients.  Socket-borne classes (intra-host / cross-host) are
    fitted from measured ``TransportKVPath`` samples by the cluster."""
    rng = np.random.default_rng(seed)
    cfg = engine.cfg
    V = cfg.vocab_size

    pre_samples = []
    for hist in hist_lens:
        for n in prefill_lens:
            if hist + n + 8 > engine.max_len:
                continue
            cache = engine.new_cache(1)
            if hist:
                htok = rng.integers(0, V, hist)
                cache, _, _ = engine.run_chunk(cache, engine.pad_chunk(htok))
            chunk = engine.pad_chunk(rng.integers(0, V, n))

            def call(c=cache, t=chunk):
                # donation invalidates the cache; rebuild via closure copy
                c2 = jax.tree.map(jnp.copy, c)
                return engine.run_chunk(c2, t)

            dt, _ = _time_call(call)
            pre_samples.append((hist, n, dt))
    perf.fit_prefill(tp, pre_samples)

    dec_samples = []
    for b in batches:
        ctx = 64
        cache = engine.new_cache(b)
        tok = jnp.asarray(rng.integers(0, V, (b, ctx)), jnp.int32)
        cache, _, _ = engine.run_chunk(cache, tok)
        step_tok = jnp.asarray(rng.integers(0, V, (b, 1)), jnp.int32)

        def call(c=cache, t=step_tok):
            c2 = jax.tree.map(jnp.copy, c)
            return engine.run_chunk(c2, t)

        dt, _ = _time_call(call)
        dec_samples.append((b, float(ctx), dt))
    perf.fit_decode(tp, dec_samples)

    if fused:
        packed = packed and engine.supports_packed
        fused_samples = []
        for ctx in (16, 48):
            for b in sorted({max(1, min(b, 3)) for b in batches}):
                rows = b + 1
                if ctx + min(prefill_lens) + 8 > engine.max_len:
                    continue          # nothing in this group can fit
                cache = engine.new_cache(rows)
                htok = jnp.asarray(rng.integers(0, V, (rows, ctx)), jnp.int32)
                cache, _, _ = engine.run_chunk(cache, htok)
                for n in prefill_lens:
                    if ctx + n + 8 > engine.max_len:
                        continue
                    if packed:
                        ptoks = rng.integers(0, V, n).astype(np.int32)
                        dtoks = rng.integers(0, V, b).astype(np.int32)
                        segs = [(0, ptoks)] + [
                            (i + 1, dtoks[i:i + 1]) for i in range(b)]

                        def call(c=cache, s=segs):
                            c2 = jax.tree.map(jnp.copy, c)
                            return engine.run_packed(c2, s)
                    else:
                        m = engine.pad_mult
                        width = ((n + m - 1) // m) * m
                        chunk = np.full((rows, width), -1, np.int32)
                        chunk[0, :n] = rng.integers(0, V, n)
                        chunk[1:, 0] = rng.integers(0, V, b)  # decoding rows

                        def call(c=cache, t=jnp.asarray(chunk)):
                            c2 = jax.tree.map(jnp.copy, c)
                            return engine.run_chunk(c2, t)

                    dt, _ = _time_call(call)
                    fused_samples.append((ctx, n, b, float(ctx), dt))
        if len(fused_samples) >= 5:
            perf.fit_fused(tp, fused_samples)

    if kv:
        from repro.serving.kv_transfer import (
            extract_range, insert_range, reshard)
        lens = [l for l in kv_lens if l + 8 <= engine.max_len]
        if lens:
            src = engine.new_cache(1)
            htok = rng.integers(0, V, max(lens))
            src, _, _ = engine.run_chunk(src, engine.pad_chunk(htok))
            dst = engine.new_cache(1)
            kv_samples = []
            for l in lens:
                def call(lo=0, hi=l):
                    ext = reshard(extract_range(src, cfg, engine.max_len,
                                                lo, hi), engine.device)
                    return insert_range(dst, ext, cfg, engine.max_len, lo, 0,
                                        replace_state=True)

                dt, _ = _time_call(call)
                kv_samples.append((l, dt))
            perf.fit_kv(kv_samples, link="intra-process")
            perf.ensure_link_monotone()
    return perf
