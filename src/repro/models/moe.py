"""Mixture-of-Experts FFN with sort-based ragged dispatch.

TPU-native design (DESIGN.md §5/§6): tokens are kept *local to their data
shard* via a partial-manual ``shard_map`` over the batch axes; inside each
shard we sort tokens by expert id and use ``jax.lax.ragged_dot`` (the TPU MoE
grouped-matmul primitive).  The ``model`` mesh axis stays in GSPMD-auto mode,
so expert weights are TP-sharded on their ``ff`` dim exactly like a dense MLP
("tp" mode — every chip holds a 1/TP slice of every expert).

"ep" mode additionally shards the *expert* dim over the ``data`` axis
(2-D expert x tensor parallelism).  This is mandatory for kimi-k2-1t: one
replica of its 1.04T params cannot fit a 16-chip TP group (DESIGN.md §5).
Tokens are exchanged with a fixed-capacity all_to_all (GShard-style); over-
capacity assignments are dropped (counted in ``moe_dropped``), matching
standard capacity-factor semantics.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import current_env
from repro.models.common import activate, spec


def moe_template(cfg, stack: Tuple[int, ...] = ()):
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    s = tuple(stack)
    sl = ("periods",) * len(s)
    return {
        "router": spec(s + (d, E), sl + ("embed", "experts"), dtype="float32"),
        "wg": spec(s + (E, d, ff), sl + ("experts", "embed", "ff")),
        "wi": spec(s + (E, d, ff), sl + ("experts", "embed", "ff")),
        "wo": spec(s + (E, ff, d), sl + ("experts", "ff", "embed")),
    }


def _topk_route(cfg, router: jax.Array, x: jax.Array):
    """x: (T, d) -> gains (T, k) fp32, ids (T, k) int32, full probs (T, E)."""
    logits = x.astype(jnp.float32) @ router.astype(jnp.float32)     # (T, E)
    k = cfg.num_experts_per_tok
    top_logits, ids = jax.lax.top_k(logits, k)                       # (T, k)
    gains = jax.nn.softmax(top_logits, axis=-1)                      # mixtral-style
    probs = jax.nn.softmax(logits, axis=-1)
    return gains, ids.astype(jnp.int32), probs


def _ragged_expert_ffn(cfg, p, xs: jax.Array, group_sizes: jax.Array,
                       model_axis: str = None) -> jax.Array:
    """xs: (N, d) sorted by expert; group_sizes: (E,). -> (N, d).

    When ``model_axis`` is given the expert weights are ff-sliced over that
    manual mesh axis (tensor-parallel experts) and the down-projection is
    psum-reduced — the whole MoE runs fully-manual inside shard_map (the
    partial-auto path trips an XLA SPMD bug on 3-axis meshes; DESIGN.md §10).
    """
    gate = jax.lax.ragged_dot(xs, p["wg"], group_sizes,
                              preferred_element_type=jnp.float32)
    up = jax.lax.ragged_dot(xs, p["wi"], group_sizes,
                            preferred_element_type=jnp.float32)
    h = activate(cfg.activation, gate, up).astype(xs.dtype)
    out = jax.lax.ragged_dot(h, p["wo"], group_sizes,
                             preferred_element_type=jnp.float32)
    if model_axis is not None:
        out = jax.lax.psum(out, model_axis)
    return out.astype(xs.dtype)


def _local_moe(cfg, p, xf: jax.Array, model_axis: str = None):
    """Token-local sort + ragged dispatch.  xf: (T, d) -> (T, d), aux dict."""
    T, d = xf.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    gains, ids, probs = _topk_route(cfg, p["router"], xf)

    flat_ids = ids.reshape(-1)                                   # (T*k,)
    sort_idx = jnp.argsort(flat_ids)                             # stable
    tok_idx = sort_idx // k                                      # (T*k,)
    xs = jnp.take(xf, tok_idx, axis=0)                           # (T*k, d)
    group_sizes = jnp.bincount(flat_ids, length=E).astype(jnp.int32)

    ys = _ragged_expert_ffn(cfg, p, xs, group_sizes, model_axis) # (T*k, d)
    w = jnp.take(gains.reshape(-1), sort_idx)[:, None].astype(ys.dtype)
    out = jnp.zeros((T, d), ys.dtype).at[tok_idx].add(ys * w)

    # Switch-style load-balance aux loss (fraction routed * mean prob)
    frac = group_sizes.astype(jnp.float32) / jnp.maximum(T * k, 1)
    mean_prob = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac * mean_prob)
    return out, aux, group_sizes


def _ep_moe(cfg, p, xf: jax.Array, expert_axis: str, n_shards: int,
            model_axis: str = None):
    """Expert-parallel MoE body (runs *inside* shard_map; xf is shard-local)."""
    T, d = xf.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    E_local = E // n_shards
    gains, ids, probs = _topk_route(cfg, p["router"], xf)

    N = T * k
    flat_ids = ids.reshape(-1)                                   # (N,)
    owner = flat_ids // E_local                                  # dest shard (N,)
    cap = max(1, int((N // n_shards) * cfg.moe_capacity_factor) + 1)

    # rank of each assignment within its destination shard (stable grouping)
    order = jnp.argsort(owner)
    sorted_owner = jnp.take(owner, order)
    first_of_group = jnp.searchsorted(sorted_owner, sorted_owner, side="left")
    rank_sorted = (jnp.arange(N) - first_of_group).astype(jnp.int32)
    pos_in_owner = jnp.zeros((N,), jnp.int32).at[order].set(rank_sorted)
    keep = pos_in_owner < cap

    # scatter into (n_shards, cap) send buffers; overflow rows -> trash slot
    slot = jnp.where(keep, owner * cap + pos_in_owner, n_shards * cap)
    src_tok = jnp.arange(N) // k
    send_x = (jnp.zeros((n_shards * cap + 1, d), xf.dtype)
              .at[slot].set(jnp.take(xf, src_tok, axis=0))[:-1]
              .reshape(n_shards, cap, d))
    send_e = (jnp.full((n_shards * cap + 1,), E, jnp.int32)
              .at[slot].set(flat_ids)[:-1]
              .reshape(n_shards, cap))

    recv_x = jax.lax.all_to_all(send_x, expert_axis, 0, 0)       # (n_shards, cap, d)
    recv_e = jax.lax.all_to_all(send_e, expert_axis, 0, 0)

    rx = recv_x.reshape(n_shards * cap, d)
    re = recv_e.reshape(n_shards * cap)
    shard_id = jax.lax.axis_index(expert_axis)
    local_e = jnp.where(re >= E, E_local, re - shard_id * E_local)  # E_local = pad bucket

    s_idx = jnp.argsort(local_e)
    rs = jnp.take(rx, s_idx, axis=0)
    group_sizes = jnp.bincount(local_e, length=E_local + 1).astype(jnp.int32)[:E_local]

    ys = _ragged_expert_ffn(cfg, p, rs, group_sizes, model_axis)
    pad_mask = (jnp.take(local_e, s_idx) < E_local)[:, None]
    ys = jnp.where(pad_mask, ys, 0.0)
    ys_unsorted = jnp.zeros_like(ys).at[s_idx].set(ys)
    back = jax.lax.all_to_all(ys_unsorted.reshape(n_shards, cap, d),
                              expert_axis, 0, 0)

    flat_back = back.reshape(n_shards * cap, d)
    safe_slot = jnp.clip(slot, 0, n_shards * cap - 1)
    gathered = jnp.where(keep[:, None],
                         jnp.take(flat_back, safe_slot, axis=0), 0.0)
    w = gains.reshape(-1)[:, None].astype(gathered.dtype)
    out = (jnp.zeros((T, d), gathered.dtype).at[src_tok].add(gathered * w)
           .astype(xf.dtype))

    frac = jnp.bincount(flat_ids, length=E).astype(jnp.float32) / jnp.maximum(N, 1)
    aux = E * jnp.sum(frac * jnp.mean(probs, axis=0))
    dropped = jnp.sum(jnp.logical_not(keep)).astype(jnp.float32)
    return out, aux, dropped


def moe_apply(cfg, p: Dict[str, jax.Array], x: jax.Array,
              expert_mode: str = "tp") -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (B, S, d) -> (B, S, d), aux metrics.  Dispatch is manual over batch."""
    B, S, d = x.shape
    env = current_env()

    batch_axes = env.rules.get("batch") if env is not None else None
    if isinstance(batch_axes, str):
        batch_axes = (batch_axes,)
    if batch_axes:
        batch_axes = tuple(a for a in batch_axes if a in env.mesh.axis_names)

    if env is None or not batch_axes:
        out, aux, _ = _local_moe(cfg, p, x.reshape(B * S, d))
        return out.reshape(B, S, d), {"moe_aux_loss": aux}

    # Fully-manual shard_map over (batch axes + model): ff is explicitly
    # sliced over the model axis and psum-combined.  Partial-auto (model left
    # to GSPMD) triggers an XLA crash on 3-axis meshes (DESIGN.md §10).
    model_axis = "model" if "model" in env.mesh.axis_names else None
    manual = set(batch_axes) | ({model_axis} if model_axis else set())
    mspec = model_axis  # None -> replicated

    expert_axes = env.rules.get("experts")
    if isinstance(expert_axes, str):
        expert_axes = (expert_axes,)
    use_ep = (expert_mode == "ep" and expert_axes
              and cfg.num_experts % env.mesh.shape[expert_axes[0]] == 0)

    bspec = P(batch_axes if len(batch_axes) > 1 else batch_axes[0], None, None)

    if use_ep:
        expert_axis = expert_axes[0]
        n_shards = env.mesh.shape[expert_axis]
        wspec = {"router": P(None, None),
                 "wg": P(expert_axis, None, mspec),
                 "wi": P(expert_axis, None, mspec),
                 "wo": P(expert_axis, mspec, None)}

        def body(xb, pb):
            Bl, Sl, _ = xb.shape
            out, aux, dropped = _ep_moe(cfg, pb, xb.reshape(Bl * Sl, d),
                                        expert_axis, n_shards, model_axis)
            aux = jax.lax.pmean(aux, batch_axes)
            dropped = jax.lax.psum(dropped, batch_axes)
            return out.reshape(Bl, Sl, d), aux, dropped

        fn = jax.shard_map(body, mesh=env.mesh, in_specs=(bspec, wspec),
                           out_specs=(bspec, P(), P()),
                           axis_names=frozenset(manual), check_vma=False)
        out, aux, dropped = fn(x, p)
        return out, {"moe_aux_loss": aux, "moe_dropped": dropped}

    # "tp" mode: tokens manual over batch axes; experts ff-sliced over model
    wspec = {"router": P(None, None),
             "wg": P(None, None, mspec),
             "wi": P(None, None, mspec),
             "wo": P(None, mspec, None)}

    def body(xb, pb):
        Bl, Sl, _ = xb.shape
        out, aux, group_sizes = _local_moe(cfg, pb, xb.reshape(Bl * Sl, d),
                                           model_axis)
        aux = jax.lax.pmean(aux, batch_axes)
        group_sizes = jax.lax.psum(group_sizes, batch_axes)
        return out.reshape(Bl, Sl, d), aux, group_sizes

    fn = jax.shard_map(body, mesh=env.mesh, in_specs=(bspec, wspec),
                       out_specs=(bspec, P(), P(None)),
                       axis_names=frozenset(manual), check_vma=False)
    out, aux, group_sizes = fn(x, p)
    return out, {"moe_aux_loss": aux, "moe_group_sizes": group_sizes}
