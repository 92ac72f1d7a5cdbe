"""Compile the served path for a described TPU v5e chip.

The TPU compiler is installed even where no chip is attached, so these
tests lower and compile the three Pallas kernels at gemma2-2b widths with
the block sizes the served path uses, and the whole dense-decode and packed
steps at full width.  A kernel that Mosaic refuses (block shapes, VMEM) or
a step that no longer holds a kernel fails here, at no chip time.  Nothing
runs: arguments are shapes placed on the described device.

The topology is described inside a fixture (never at import, in a skipif
or a parametrize), and only the xdist worker given this file loads the TPU
library.  The ops wrappers take their kernel branch only on a ``tpu``
backend, so each test patches ``jax.default_backend`` for its own trace.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attn.ops import decode_attention
from repro.kernels.flash_prefill.ops import flash_attention
from repro.kernels.ragged_fused.ops import PACK_ALIGN_TPU, ragged_attention
from repro.models import build_model
from repro.models.packed import forward_packed
from repro.models.transformer import _attn_scale, forward_cached, init_cache

B, T, S, P = 8, 2048, 256, 256     # slots, max_len, prefill chunk, pack


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture
def tpu_backend(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.fixture(scope="module")
def cfg():
    return get_config("gemma2-2b")


def _shape(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _attn_kw(cfg):
    return dict(scale=_attn_scale(cfg), attn_softcap=cfg.attn_logit_softcap)


def test_flash_prefill_compiles_at_b8(one_chip, tpu_backend, cfg):
    H, G, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    fn = functools.partial(flash_attention, causal=True, **_attn_kw(cfg))
    text = _compiled_text(
        lambda q, k, v, qp, kp: fn(q, k, v, q_positions=qp, kv_positions=kp),
        _shape(one_chip, (B, S, H, hd), jnp.bfloat16),
        _shape(one_chip, (B, T, G, hd), jnp.bfloat16),
        _shape(one_chip, (B, T, G, hd), jnp.bfloat16),
        _shape(one_chip, (B, S)), _shape(one_chip, (B, T)))
    assert "tpu_custom_call" in text


def test_decode_attn_compiles_at_b8(one_chip, tpu_backend, cfg):
    H, G, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    fn = functools.partial(decode_attention, **_attn_kw(cfg))
    text = _compiled_text(
        lambda q, k, v, qp, kp: fn(q, k, v, q_positions=qp, kv_positions=kp),
        _shape(one_chip, (B, 1, H, hd), jnp.bfloat16),
        _shape(one_chip, (B, T, G, hd), jnp.bfloat16),
        _shape(one_chip, (B, T, G, hd), jnp.bfloat16),
        _shape(one_chip, (B, 1)), _shape(one_chip, (B, T)))
    assert "tpu_custom_call" in text


def test_ragged_fused_compiles_at_pack_block(one_chip, tpu_backend, cfg):
    H, G, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    fn = functools.partial(ragged_attention, causal=True,
                           block_q=PACK_ALIGN_TPU, **_attn_kw(cfg))
    text = _compiled_text(
        lambda q, k, v, rows, qp, kp: fn(q, k, v, q_rows=rows,
                                         q_positions=qp, kv_positions=kp),
        _shape(one_chip, (P, H, hd), jnp.bfloat16),
        _shape(one_chip, (B, T, G, hd), jnp.bfloat16),
        _shape(one_chip, (B, T, G, hd), jnp.bfloat16),
        _shape(one_chip, (P,)), _shape(one_chip, (P,)),
        _shape(one_chip, (B, T)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("step", ["dense_decode", "packed"])
def test_served_step_compiles_at_full_width(one_chip, tpu_backend, cfg,
                                            step):
    """The whole jitted step at gemma2-2b widths: 26 layers, bf16 weights,
    an 8-slot 2048-token cache.  Also bounds its memory by one chip's."""
    def place(tree):
        return jax.tree.map(lambda x: _shape(one_chip, x.shape, x.dtype),
                            tree)

    params = place(jax.eval_shape(build_model(cfg).init,
                                  jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: init_cache(cfg, B, T)))
    if step == "packed":
        fn = functools.partial(forward_packed, cfg)
        args = (params, cache, _shape(one_chip, (P,)),
                _shape(one_chip, (P,)), _shape(one_chip, (P,)),
                _shape(one_chip, (8,)))
    else:
        fn = functools.partial(forward_cached, cfg)
        args = (params, cache, _shape(one_chip, (B, 1)))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < 16e9, used
