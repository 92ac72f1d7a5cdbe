"""Logical-axis sharding rules (MaxText-style) for the whole framework.

Model code never names mesh axes directly.  It annotates activations with
*logical* axis names (``shard(x, "batch", "seq", "embed")``) and parameter
templates carry logical tuples.  A ``ShardingEnv`` maps logical names to mesh
axes; swapping the mapping is how §Perf iterations change sharding without
touching model code.

Shape-aware assignment: jit input/output shardings must divide evenly, and a
mesh axis may appear once per PartitionSpec.  ``ShardingEnv.sharding`` takes
the tensor shape and assigns each mesh axis greedily to the highest-priority
logical dim that (a) requests it and (b) divides.  This is what makes one
rule-set serve every arch: 64-head models get Megatron head-parallel
attention; 40/24/10/8-head models fall back to sequence/context parallelism
(q seq-sharded for prefill/train, KV-cache seq-sharded for decode) and
row-parallel attention projections ("attn_in"/"o_hd") — all automatically.

Logical axes:

  batch      token batch                 -> ("pod", "data")
  seq        activation sequence         -> "model" (train/prefill SP fallback)
  embed      d_model / residual stream   -> None (FSDP: "data" on params)
  heads      query heads                 -> "model" (wins over seq if divisible)
  kv_heads   KV heads                    -> usually non-divisible -> dropped
  kv_seq     KV-cache sequence           -> "model" (context-parallel decode)
  attn_in    d_model input of wq/wk/wv   -> "model" (row-parallel fallback)
  o_hd       head_dim contraction of wo  -> "model" (row-parallel fallback)
  ff         MLP hidden                  -> "model"
  vocab      embedding/unembedding rows  -> "model"
  experts    MoE expert dim              -> "data" in EP mode
  ssm_heads  Mamba-2 SSD heads           -> "model" (24 on 16 -> dropped)
  lru        RG-LRU channel dim          -> "model"
  + inert axes (conv_k, state, head_dim, img_seq, periods, window) -> None
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass
from typing import Dict, Mapping, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisVal = Union[None, str, Tuple[str, ...]]

# Lower value = assigned first when several dims want the same mesh axis.
# seq beats kv_seq so prefill/train logits shard on q-seq (SP) while decode
# (where the seq rule is off) falls through to kv-seq (context parallel).
_PRIORITY: Dict[str, int] = {
    "batch": 0,
    "heads": 1, "vocab": 1, "ff": 1, "ssm_heads": 1, "lru": 1, "experts": 1,
    "kv_heads": 2,
    "seq": 3,
    "kv_seq": 4,
    "attn_in": 5, "o_hd": 5,
    "embed": 6,
}
_DEFAULT_PRIORITY = 7


@dataclass(frozen=True)
class ShardingEnv:
    mesh: Mesh
    rules: Mapping[str, AxisVal]

    def _assign(self, logical: Sequence[Optional[str]],
                shape: Optional[Sequence[int]]) -> list:
        parts: list = [None] * len(logical)
        used: set = set()
        order = sorted(range(len(logical)),
                       key=lambda i: (_PRIORITY.get(logical[i] or "",
                                                    _DEFAULT_PRIORITY), i))
        for i in order:
            name = logical[i]
            if name is None:
                continue
            ax = self.rules.get(name)
            if ax is None:
                continue
            axes = (ax,) if isinstance(ax, str) else tuple(ax)
            got = []
            running = 1
            for a in axes:
                if a in used or a not in self.mesh.axis_names:
                    continue
                size = self.mesh.shape[a]
                if shape is not None and shape[i] % (running * size) != 0:
                    continue
                got.append(a)
                used.add(a)
                running *= size
            if not got:
                continue
            parts[i] = got[0] if len(got) == 1 else tuple(got)
        return parts

    def spec(self, logical: Sequence[Optional[str]],
             shape: Optional[Sequence[int]] = None) -> P:
        return P(*self._assign(logical, shape))

    def sharding(self, logical: Sequence[Optional[str]],
                 shape: Optional[Sequence[int]] = None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(logical, shape))


_state = threading.local()


def current_env() -> Optional[ShardingEnv]:
    return getattr(_state, "env", None)


@contextlib.contextmanager
def axis_rules(env: Optional[ShardingEnv]):
    prev = getattr(_state, "env", None)
    _state.env = env
    try:
        yield env
    finally:
        _state.env = prev


def shard(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """Annotate an activation with logical axes (no-op without an env)."""
    env = current_env()
    if env is None:
        return x
    if x.ndim != len(logical):
        raise ValueError(f"rank {x.ndim} != logical {logical}")
    return jax.lax.with_sharding_constraint(
        x, env.sharding(logical, x.shape))


def logical_spec(*logical: Optional[str]) -> P:
    env = current_env()
    if env is None:
        return P()
    return env.spec(logical)


def named_sharding(mesh: Mesh, *parts) -> NamedSharding:
    return NamedSharding(mesh, P(*parts))


# ---------------------------------------------------------------------------
# Rule presets
# ---------------------------------------------------------------------------

def make_rules(
    *,
    mode: str,                       # "train" | "prefill" | "decode"
    data_axes: Tuple[str, ...] = ("data",),
    model_axis: str = "model",
    seq_shard_activations: bool = True,    # SP / context-parallel fallback
    kv_seq_shard: bool = True,             # seq-sharded KV caches (serve)
    expert_sharding: str = "tp",           # "tp" (ff on model) | "ep" (experts on data)
    shard_heads: bool = True,
    batch_shardable: bool = True,          # False for batch=1 long-context cells
) -> Dict[str, AxisVal]:
    batch: AxisVal = tuple(data_axes) if batch_shardable else None
    seq_ok = seq_shard_activations and mode != "decode"
    if mode == "train":
        kv_seq_shard = False     # no cache in train; keep T unsharded
    if mode == "decode" and kv_seq_shard:
        # Context-parallel decode: head sharding would force an all-gather of
        # the seq-sharded KV cache EVERY layer (GiB/layer); with heads off,
        # logits shard on kv_seq and softmax combines via two tiny psums
        # (flash-decoding).  §Perf cell A, iteration 1.
        shard_heads = False
    rules: Dict[str, AxisVal] = {
        "batch": batch,
        "seq": model_axis if seq_ok else None,
        "embed": None,
        "heads": model_axis if shard_heads else None,
        "kv_heads": model_axis if shard_heads else None,
        "kv_seq": model_axis if kv_seq_shard else None,
        "attn_in": model_axis,
        "o_hd": model_axis,
        "ff": model_axis,
        "vocab": model_axis,
        "experts": tuple(data_axes) if expert_sharding == "ep" else None,
        "ssm_heads": model_axis,
        "lru": model_axis,
        # inert
        "conv_k": None,
        "state": None,
        "head_dim": None,
        "img_seq": None,
        "periods": None,
        "window": None,
    }
    return rules
