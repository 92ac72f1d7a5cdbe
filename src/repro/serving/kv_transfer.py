"""Session-state (KV / SSM / LRU) transfer between workers.

The TPU adaptation of NIXL point-to-point RDMA (paper §6): cache slices move
between workers as explicit ``jax.device_put`` onto the destination
engine's device (``reshard``) — chip to chip when in-process workers sit on
different devices, a no-op on one device, where the byte accounting and the
lazy-read/incremental-write protocol stay real:

  * ``extract_range``    pull a [lo, hi) token range of one batch row —
    seq-dim slices for full-attention K/V + positions, whole-state copies
    for recurrent/ring/cross state.  Used for both the *incremental KV*
    (prefill -> decode; only the increment moves, §6 footnote 4) and the
    *lazy history read* (decode -> prefill).
  * ``insert_range``     merge an extract into a batched decode-cache slot
    (the decode worker's local prefix-cache merge).
  * ``transfer_bytes``   exact payload size, fed to windowed stats and
    compared against the perf model's T_kv.

Cache layout note: leaves under ``stacked`` carry a leading layer-period dim
(n_per, B, ...); root/``rest`` leaves are batch-leading.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

Cache = Dict[str, Any]

_SEQ_LEAVES = ("k", "v", "pos_full")


def _map_cache(cache, fn, path=()):
    if isinstance(cache, dict):
        return {k: _map_cache(v, fn, path + (k,)) for k, v in cache.items()}
    return fn(path, cache)


def _axes(path: Tuple[str, ...]) -> int:
    """Batch axis of a cache leaf (stacked leaves have a leading period dim)."""
    return 1 if path and path[0] == "stacked" else 0


def _is_seq_leaf(path, x, max_len: int, b_ax: int) -> bool:
    return (path[-1] in _SEQ_LEAVES and x.ndim > b_ax + 1
            and x.shape[b_ax + 1] == max_len)


def extract_range(cache: Cache, cfg: ModelConfig, max_len: int,
                  lo: int, hi: int, row: int = 0) -> Cache:
    """Token range [lo, hi) of one batch row (keeps a singleton batch dim)."""
    n = hi - lo

    def leaf(path, x):
        b_ax = _axes(path)
        xr = jax.lax.slice_in_dim(x, row, row + 1, axis=b_ax)
        if _is_seq_leaf(path, x, max_len, b_ax):
            return jax.lax.dynamic_slice_in_dim(xr, lo, n, axis=b_ax + 1)
        return xr  # ring / recurrent state / cross KV / length: full copy

    return _map_cache(cache, leaf)


def insert_range(dst: Cache, src: Cache, cfg: ModelConfig, max_len: int,
                 lo: int, slot: int, *, replace_state: bool) -> Cache:
    """Write ``src`` (a 1-row extract) into batch row ``slot`` of ``dst``.

    Seq-sliced leaves land at token offset ``lo``; everything else replaces
    the slot's value when ``replace_state`` (an increment's final recurrent
    state subsumes the old one)."""
    def leaf_pair(path, d):
        s = _get(src, path)
        b_ax = _axes(path)
        if (_is_seq_leaf(path, d, max_len, b_ax)
                and s.shape[b_ax + 1] != d.shape[b_ax + 1]):
            if b_ax == 0:
                row = jax.lax.dynamic_update_slice_in_dim(
                    d[slot], s[0], lo, axis=0)
                return d.at[slot].set(row)
            row = jax.lax.dynamic_update_slice_in_dim(
                d[:, slot], s[:, 0], lo, axis=1)
            return d.at[:, slot].set(row)
        if not replace_state and path[-1] not in ("length",) + _SEQ_LEAVES:
            return d
        if b_ax == 0:
            return d.at[slot].set(s[0])
        return d.at[:, slot].set(s[:, 0])

    return _map_cache(dst, leaf_pair)


def _get(tree, path):
    for p in path:
        tree = tree[p]
    return tree


def _is_extract_seq_leaf(path, x, b_ax: int) -> bool:
    """Seq-leaf test for EXTRACT trees, where the token axis is span-length
    (not max_len) — identification is by name.  Only sound on pure
    full-attention caches (every k/v is seq-sliced); ring/recurrent state
    leaves share names but not semantics, which is why the KV pool
    (DESIGN.md §17) gates on the arch pattern."""
    return path[-1] in _SEQ_LEAVES and x.ndim > b_ax + 1


def slice_extract(tree: Cache, base_lo: int, lo: int, hi: int) -> Cache:
    """Token sub-range [lo, hi) of an extract covering [base_lo, ...) —
    page slicing for the KV pool's material store (DESIGN.md §17)."""
    def leaf(path, x):
        b_ax = _axes(path)
        if _is_extract_seq_leaf(path, x, b_ax):
            return jax.lax.slice_in_dim(x, lo - base_lo, hi - base_lo,
                                        axis=b_ax + 1)
        return x
    return _map_cache(tree, leaf)


def concat_extracts(parts, total_len: int) -> Cache:
    """Concatenate extracts of ADJACENT token ranges into one (DESIGN.md
    §17): seq leaves join on the token axis; non-seq leaves (per-row
    length, any whole-state copy) come from the LAST part — the suffix
    closest to the live row — with the length leaf pinned to
    ``total_len`` so downstream ``insert_range`` sees a coherent row."""
    last = parts[-1]

    def leaf(path, x):
        b_ax = _axes(path)
        if _is_extract_seq_leaf(path, x, b_ax):
            if len(parts) == 1:
                return x
            return jnp.concatenate([_get(p, path) for p in parts],
                                   axis=b_ax + 1)
        if path[-1] == "length":
            return jnp.full_like(x, total_len)
        return x

    return _map_cache(last, leaf)


def transfer_bytes(tree: Cache) -> int:
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves(tree))


def steal_handoff(cfg: ModelConfig, task, session, src_worker,
                  dst_worker) -> int:
    """Byte accounting for a QUEUED prefill task migrating between prefill
    workers (work stealing, DESIGN.md §12).

    Nothing materialized moves at steal time — the canonical KV lives on
    the session's bound decode worker and is lazily pulled where the chunk
    actually runs (``extract_range`` at execution); chunk-chain affinity
    invalidation is owned by ``ExecutionBackend.on_steal`` (one copy for
    both backends).  This returns the history payload in bytes the thief
    will now re-read from the decode worker — the KV-locality penalty the
    Coordinator charged when it accepted the steal.
    """
    if task.l_hist <= 0:
        return 0
    return cfg.session_state_bytes(task.l_hist)


def migrate_handoff(cfg: ModelConfig, task, session, src_worker,
                    dst_worker) -> int:
    """Byte accounting for a queued LOCAL prefill chunk migrating from a
    saturated decode worker to a prefill worker (decode-local offload,
    DESIGN.md §14).

    The phase-boundary twin of :func:`steal_handoff`: nothing materialized
    moves at migration time — the canonical KV stays on the bound decode
    worker, and when the chunk executes on the destination the history is
    lazily pulled (``extract_range`` / ``kv_get``) and the increment is
    written back (``insert_range`` / ``kv_put``): under the proc transport
    both legs are real bytes over the RPC socket, measured by
    :class:`TransportKVPath`.  This returns the history payload the
    destination must now re-read — the ``t_kv(l_hist)`` penalty
    ``plan_offload`` charged when it accepted the move.  Local execution
    would have paid neither leg, which is exactly why the Coordinator only
    migrates when the decode side is saturated.

    The byte accounting itself is the steal formula (one definition, so
    the two counters cannot drift); what distinguishes migration — the
    phase boundary, and a destination death propagating instead of being
    swallowed — lives in the callers.
    """
    return steal_handoff(cfg, task, session, src_worker, dst_worker)


class TransportKVPath:
    """Measured KV movement between worker *processes* (DESIGN.md §13/§16).

    Under ``LiveCluster(transport="proc"|"tcp")`` every KV hop is real bytes
    over the RPC socket — the incremental write-back (prefill -> decode),
    the lazy history read (decode -> prefill), and the coordinator relay leg
    in between — and this object is the single account of them: exact
    payload bytes (``transfer_bytes`` of the tree that moved) and wall-clock
    seconds, measured around the blocking RPC, not modeled.  The in-process
    transport keeps the same protocol with ``jax.device_put`` copies; there
    the path stays unused and the modeled/measured T_kv comparison of
    ``benchmarks/fig12_transport.py`` is the reproduction target.

    Heterogeneous topology (§16): each worker's coordinator link carries a
    link class (``tag``, from the transport registry + the worker's hello
    host), every transfer is attributed to its class, and the per-class
    ``(payload bytes, seconds)`` samples feed
    ``PerfModel.fit_kv_from_bytes`` — the measured side of the per-class
    ``t_kv`` coefficients the scheduler prices.
    """

    def __init__(self, default_class: str = "intra-host"):
        self.bytes_moved = 0
        self.seconds = 0.0
        self.transfers = 0
        self.default_class = default_class
        #: (kind, idx) -> link class of that worker's coordinator link
        self.link_classes: Dict[Tuple[str, int], str] = {}
        #: per-class accounting mirror of the three totals above
        self.by_class: Dict[str, Dict[str, float]] = {}
        #: per-class (payload bytes, seconds) fit samples
        self.samples: Dict[str, list] = {}

    @property
    def ms(self) -> float:
        return self.seconds * 1e3

    def tag(self, kind: str, idx: int, link_class: str) -> None:
        """Record the measured link class of one worker's coordinator link."""
        self.link_classes[(kind, idx)] = link_class

    def class_of(self, client) -> str:
        """Link class of a worker RPC client (kind/idx-tagged)."""
        return self.link_classes.get(
            (getattr(client, "kind", None), getattr(client, "idx", None)),
            self.default_class)

    def account(self, nbytes: int, seconds: float,
                link: Optional[str] = None) -> None:
        self.bytes_moved += int(nbytes)
        self.seconds += float(seconds)
        self.transfers += 1
        c = link or self.default_class
        agg = self.by_class.setdefault(
            c, {"bytes": 0, "seconds": 0.0, "transfers": 0})
        agg["bytes"] += int(nbytes)
        agg["seconds"] += float(seconds)
        agg["transfers"] += 1
        self.samples.setdefault(c, []).append((int(nbytes), float(seconds)))

    def put(self, client, slot: int, lo: int, tree: Cache) -> float:
        """Incremental KV write-back into a decode worker's cache slot
        (blocking RPC; returns measured seconds)."""
        import time
        t0 = time.perf_counter()
        client.call("kv_put", slot=slot, lo=lo, tree=_numpy_tree(tree))
        dt = time.perf_counter() - t0
        self.account(transfer_bytes(tree), dt, link=self.class_of(client))
        return dt

    def get(self, client, slot: int, lo: int, hi: int) -> Cache:
        """Lazy history read out of a decode worker's cache slot."""
        import time
        t0 = time.perf_counter()
        tree = client.call("kv_get", slot=slot, lo=lo, hi=hi)
        self.account(transfer_bytes(tree), time.perf_counter() - t0,
                     link=self.class_of(client))
        return tree


def _numpy_tree(tree: Cache) -> Cache:
    """Materialize device arrays as numpy before they hit the RPC encoder."""
    return jax.tree.map(lambda x: np.asarray(x), tree)


def reshard(tree: Cache, device: Optional[jax.Device] = None) -> Cache:
    """Move a cache tree onto the destination engine's device.

    Between in-process workers placed on different chips this is the
    chip-to-chip copy of the KV handoff; on one device it moves nothing.
    ``device=None`` (an unplaced engine) means JAX's default device.
    """
    return jax.device_put(tree, device)
