"""The chip path hides no device: transports, placement, compile cache.

* the proc/tcp transports refuse a parent that holds the TPU, and their
  children run on the parent's platform instead of dropping to the CPU;
* ``packed=True`` on an arch without a ragged pack is refused;
* the compile-cache helper honours ``JAX_COMPILATION_CACHE_DIR`` and
  otherwise names one fixed directory inside the checkout;
* in-process workers sit one per device and KV handoffs land on the
  destination engine's device (four virtual CPU devices in a subprocess,
  since the device count is fixed before JAX starts).
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import get_config
from repro.launch import compile_cache
from repro.serving.worker_proc import ProcWorkerPool, _child_env

REPO = Path(__file__).resolve().parents[1]


def test_proc_pool_refuses_a_tpu_parent(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("qwen2.5-14b").reduced()
    for kind in ("proc", "tcp"):
        with pytest.raises(RuntimeError, match="already holds the TPU"):
            ProcWorkerPool(cfg, max_len=64, transport=kind)


def test_proc_pool_refuses_packed_on_an_arch_without_a_pack():
    cfg = get_config("mamba2-130m").reduced()
    with pytest.raises(ValueError, match="no ragged pack"):
        ProcWorkerPool(cfg, max_len=64, packed=True)


@pytest.mark.parametrize("parent", ["cpu", "gpu"])
def test_children_run_on_the_parent_platform(monkeypatch, parent):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: parent)
    env = _child_env(tp=1)
    assert env["JAX_PLATFORMS"] == parent
    assert env["PYTHONPATH"].split(os.pathsep)[0] == str(REPO / "src")
    assert "xla_force_host_platform_device_count" not in env.get(
        "XLA_FLAGS", "")
    assert ("--xla_force_host_platform_device_count=2"
            in _child_env(tp=2)["XLA_FLAGS"])


@pytest.fixture
def cache_dir_config():
    """Restore JAX's cache directory after a test that points it."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path,
                                               cache_dir_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # set nothing


def test_compile_cache_has_one_fixed_path(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    second = compile_cache.enable_compile_cache()
    assert first == second == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first


_PLACEMENT = r"""
import json
import jax
import jax.numpy as jnp
from repro.configs import get_config
from repro.core.types import SLOSpec
from repro.serving import ClusterSpec, LiveCluster, make_live_sessions
from repro.serving.kv_transfer import reshard

cfg = get_config("gemma2-2b").reduced()
devs = jax.local_devices()


def serve(devices):
    cl = LiveCluster(cfg, spec=ClusterSpec(n_prefill=2, n_decode=2,
                                           max_slots=2, max_len=64),
                     slo=SLOSpec(1e6, 1e6), profile=False, devices=devices)
    ss = make_live_sessions(cfg, num_sessions=3, rounds=2, prefill_len=12,
                            incr_len=4, decode_len=3, arrival_gap=0.5)
    res = cl.run(ss)
    workers = cl.prefill_workers + cl.decode_workers
    held = []
    for w in workers:
        leaves = jax.tree.leaves(w.engine.params)
        leaves += jax.tree.leaves(getattr(w, "cache", {}))
        held.append(sorted({d.id for x in leaves for d in x.devices()}))
    return dict(engine=[w.engine.device.id for w in workers], held=held,
                finished=all(s.finish_time is not None for s in ss),
                kv=res.kv_bytes_moved,
                tokens=[list(map(int, s.generated)) for s in ss])


moved = reshard({"k": jnp.zeros((2, 3))}, devs[3])
print(json.dumps(dict(n=len(devs), spread=serve(devs),
                      one=serve([devs[0]]),
                      reshard=sorted(d.id for d in moved["k"].devices()))))
"""


def test_inproc_workers_one_per_device_and_kv_follows():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(REPO / "src"))
    out = subprocess.run([sys.executable, "-c", _PLACEMENT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["n"] == 4
    spread, one = r["spread"], r["one"]
    # prefill workers take devices 0, 1; decode workers 2, 3
    assert spread["engine"] == [0, 1, 2, 3]
    assert spread["held"] == [[0], [1], [2], [3]]
    assert one["engine"] == [0, 0, 0, 0] and one["held"] == [[0]] * 4
    assert spread["finished"] and one["finished"]
    # every increment crossed from a prefill device to a decode device
    assert spread["kv"] > 0
    assert spread["tokens"] == one["tokens"]
    assert r["reshard"] == [3]
