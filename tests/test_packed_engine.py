"""Packed (ragged) fused path: engine parity, upload regression, cluster
decision-log parity, and T_fused cost-layer inheritance (DESIGN.md §15)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.perf_model import PerfModel
from repro.core.types import PrefillTask, RoundSpec, SLOSpec
from repro.models.packed import supports_packed
from repro.runtime.chunk_tuner import ChunkTuner
from repro.serving.cluster import LiveCluster, make_live_sessions
from repro.serving.config import ClusterSpec, SchedPolicy
from repro.serving.engine import Engine, chunk_limit, profile_engine
from repro.serving.workers import LiveDecodeWorker, LiveSession


@pytest.fixture(scope="module", params=["qwen3-32b", "gemma2-2b"])
def engine(request):
    cfg = get_config(request.param).reduced()
    return Engine(cfg, max_len=128, key=jax.random.PRNGKey(0))


def _seed_histories(eng, B, hists, rng):
    cache = eng.new_cache(B)
    V = eng.cfg.vocab_size
    for i, h in enumerate(hists):
        toks = np.full((B, max(hists) + 3), -1, np.int32)
        toks[i, :h] = rng.integers(0, V, h)
        cache, _, _ = eng.run_chunk(cache, jnp.asarray(toks))
    return cache


def test_run_packed_matches_dense_fused_step(engine):
    """One packed launch == the dense rectangle: same cache lengths, same
    position maps, same per-segment logits."""
    eng = engine
    rng = np.random.default_rng(0)
    V = eng.cfg.vocab_size
    B = 4
    cache_d = _seed_histories(eng, B, [13, 7, 21, 5], rng)
    cache_p = jax.tree.map(jnp.copy, cache_d)

    ptoks = rng.integers(0, V, 11).astype(np.int32)
    dtoks = rng.integers(0, V, 3).astype(np.int32)
    chunk = np.full((B, 16), -1, np.int32)
    chunk[0, :11] = ptoks
    for i in range(3):
        chunk[i + 1, 0] = dtoks[i]
    cache_d, logits_d, _ = eng.run_chunk(cache_d, jnp.asarray(chunk))

    segs = [(0, ptoks)] + [(i + 1, dtoks[i:i + 1]) for i in range(3)]
    cache_p, seg_logits, _ = eng.run_packed(cache_p, segs)

    assert (np.asarray(cache_d["length"])
            == np.asarray(cache_p["length"])).all()
    np.testing.assert_allclose(np.asarray(seg_logits, np.float32),
                               np.asarray(logits_d, np.float32),
                               atol=2e-4, rtol=2e-4)
    for k in ("pos_full", "pos_ring"):
        if k in cache_d:
            md, mp = np.asarray(cache_d[k]), np.asarray(cache_p[k])
            # slots never written differ only in which invalid they carry
            assert ((md == mp) | ((md < -2**29) & (mp < -2**29))).all()


def test_run_packed_rejects_bad_packs(engine):
    eng = engine
    cache = eng.new_cache(2)
    with pytest.raises(AssertionError):
        eng.run_packed(cache, [])
    with pytest.raises(AssertionError):   # duplicate rows
        eng.run_packed(cache, [(0, np.zeros(4, np.int32)),
                               (0, np.zeros(1, np.int32))])
    with pytest.raises(AssertionError):   # over the chunk limit
        lim = chunk_limit(eng.cfg, eng.max_len)
        eng.run_packed(cache, [(0, np.zeros(lim + 1, np.int32))])


def test_packed_unsupported_arch_gated():
    cfg = get_config("mamba2-130m").reduced()
    assert not supports_packed(cfg)
    eng = Engine(cfg, max_len=64, key=jax.random.PRNGKey(0))
    assert not eng.supports_packed
    with pytest.raises(AssertionError):
        eng.run_packed(eng.new_cache(1), [(0, np.zeros(4, np.int32))])
    # asking for the packed path on an arch without a ragged pack is refused
    # (never served dense in silence); auto and explicit-off go dense
    with pytest.raises(ValueError, match="no ragged pack"):
        LiveDecodeWorker(0, eng, max_slots=2, packed=True)
    assert not LiveDecodeWorker(0, eng, max_slots=2).packed
    assert not LiveDecodeWorker(0, eng, max_slots=2, packed=False).packed


# ---------------------------------------------------------------------------
# upload accounting (satellite: sub-chunk waste fix)
# ---------------------------------------------------------------------------

def _mk_task(sid, toks):
    return PrefillTask(session_id=sid, round_idx=0, l_hist=0,
                       l_incr=len(toks), enqueue_time=0.0, arrival_time=0.0,
                       is_initial=True)


def _fused_scenario(cfg, packed, n_chunk=50, n_dec=3, max_slots=4):
    eng = Engine(cfg, max_len=128, key=jax.random.PRNGKey(0))
    w = LiveDecodeWorker(0, eng, max_slots=max_slots, packed=packed)
    rng = np.random.default_rng(1)
    V = cfg.vocab_size
    batch = []
    for i in range(1, n_dec + 1):
        toks = rng.integers(0, V, 6).astype(np.int32)
        s = LiveSession(session_id=i, arrival_time=0.0,
                        rounds=[RoundSpec(6, 4)], prompt_tokens=[toks])
        w.slots[i] = s
        s.slot = i
        _, first = w.local_prefill(_mk_task(i, toks), s)
        s.last_token = first
        batch.append(s)
    toks0 = rng.integers(0, V, n_chunk).astype(np.int32)
    s0 = LiveSession(session_id=9, arrival_time=0.0,
                     rounds=[RoundSpec(n_chunk, 4)], prompt_tokens=[toks0])
    w.slots[0] = s0
    s0.slot = 0
    up0 = eng.tokens_uploaded
    dt, first, toks = w.fused_step(_mk_task(9, toks0), s0, batch)
    return eng, w, s0, batch, first, toks, eng.tokens_uploaded - up0


def test_fused_step_upload_regression():
    """A fused step spanning multiple sub-chunks must upload
    sum(width_i + max_slots) token elements — NEVER re-materialize the
    (max_slots, width) rectangle for sub-chunks whose decode rows do not
    advance (the old path shipped n_sub * max_slots * width)."""
    cfg = get_config("gemma2-2b").reduced()   # window 32 < chunk 50 -> 2 subs
    n_chunk, max_slots = 50, 4
    eng, w, *_, uploaded = _fused_scenario(cfg, packed=False,
                                           n_chunk=n_chunk,
                                           max_slots=max_slots)
    lim = chunk_limit(cfg, eng.max_len)
    assert lim < n_chunk, "scenario must span >1 sub-chunk"
    m = eng.pad_mult
    expect, rect = 0, 0
    for lo in range(0, n_chunk, lim):
        width = ((min(lim, n_chunk - lo) + m - 1) // m) * m
        expect += width + max_slots
        rect += max_slots * width
    assert uploaded == expect, (uploaded, expect)
    assert uploaded < rect            # strictly better than the rectangle


def test_packed_fused_step_upload_counts():
    """The packed step uploads one shape-bucketed stream per sub-chunk."""
    from repro.kernels.ragged_fused.ops import pack_layout

    cfg = get_config("gemma2-2b").reduced()
    n_chunk, n_dec = 50, 3
    eng, w, *_, uploaded = _fused_scenario(cfg, packed=True, n_chunk=n_chunk,
                                           n_dec=n_dec)
    lim = chunk_limit(cfg, eng.max_len)
    expect = 0
    first = True
    for lo in range(0, n_chunk, lim):
        lens = [min(lim, n_chunk - lo)] + ([1] * n_dec if first else [])
        _, total = pack_layout(lens, eng.pack_align)
        expect += eng.packed_bucket(total)
        first = False
    assert uploaded == expect, (uploaded, expect)


def test_packed_vs_dense_worker_tokens():
    """Same tokens out of both fused paths, including multi-sub chunks."""
    cfg = get_config("gemma2-2b").reduced()
    _, _, s0_d, batch_d, first_d, toks_d, _ = _fused_scenario(cfg, False)
    _, _, s0_p, batch_p, first_p, toks_p, _ = _fused_scenario(cfg, True)
    assert first_d == first_p
    assert toks_d == toks_p


# ---------------------------------------------------------------------------
# cluster decision-log parity (packed=True vs packed=False)
# ---------------------------------------------------------------------------

def _run_cluster(cfg, packed):
    cl = LiveCluster(cfg,
                     spec=ClusterSpec(n_prefill=1, n_decode=1, max_slots=4,
                                      max_len=128),
                     policy=SchedPolicy(packed=packed, chunk_tokens=16),
                     profile=False, slo=SLOSpec(10.0, 10.0))
    cl.coordinator.record_decisions = True
    # arrival gap >> any engine duration: event order (hence the decision
    # log) is protocol-determined, not timing-determined — the same device
    # that makes the multiproc golden stable makes this parity exact.
    sessions = make_live_sessions(cfg, num_sessions=3, rounds=2,
                                  prefill_len=20, decode_len=4,
                                  arrival_gap=100.0)
    res = cl.run(sessions)
    return (res, list(cl.coordinator.decision_log),
            [list(map(int, s.generated)) for s in sessions])


def test_cluster_decision_log_parity():
    cfg = get_config("gemma2-2b").reduced()
    res_d, log_d, toks_d = _run_cluster(cfg, packed=False)
    res_p, log_p, toks_p = _run_cluster(cfg, packed=True)
    assert not res_d.packed and res_p.packed
    assert log_d == log_p
    assert toks_d == toks_p
    assert res_p.tokens_uploaded > 0
    # SLO accounting survives the swap
    assert res_p.slo_attainment == res_d.slo_attainment == 1.0


# ---------------------------------------------------------------------------
# cost-layer inheritance: packed profile -> T_fused -> tuner
# ---------------------------------------------------------------------------

def test_t_fused_refit_and_tuner_inheritance():
    cfg = get_config("qwen3-32b").reduced()
    eng = Engine(cfg, max_len=256, key=jax.random.PRNGKey(0))
    assert eng.supports_packed

    perf_d, perf_p = PerfModel(cfg), PerfModel(cfg)
    up0 = eng.tokens_uploaded
    profile_engine(eng, perf_d, tp=1, prefill_lens=(16, 32, 64),
                   hist_lens=(0, 32), batches=(1, 3), fused=True,
                   packed=False)
    up1 = eng.tokens_uploaded
    profile_engine(eng, perf_p, tp=1, prefill_lens=(16, 32, 64),
                   hist_lens=(0, 32), batches=(1, 3), fused=True,
                   packed=True)
    # the packed profile really drove run_packed (uploads counted per pack;
    # the dense profile calls run_chunk directly and counts nothing)
    assert up1 == up0 and eng.tokens_uploaded > up1

    # both fits are MEASURED (no analytic re-derivation)
    assert 1 in perf_d._fused_fitted and 1 in perf_p._fused_fitted

    # sane, finite fits at the piggyback shape.  NOTE: the profiler clamps
    # fused sampling to batch <= 3, where the CPU ref path's gather overhead
    # can eat the packing win — the packed>dense PERF gate lives in
    # benchmarks/kernel_bench.py --smoke at the full 8-row piggyback shape;
    # here we only bound gross regressions (CI timing, not a benchmark).
    shape = dict(l_hist=32, l_incr=64, batch=3, tp=1, avg_ctx=32.0)
    t_d, t_p = perf_d.t_fused(**shape), perf_p.t_fused(**shape)
    assert t_p > 0.0 and t_d > 0.0
    assert t_p <= 3.0 * t_d, (t_p, t_d)

    # ChunkTuner inverts whichever fit it is handed — T_fused-driven chunk
    # decisions consume the MEASURED packed coefficients, and a larger ITL
    # budget can never shrink the chunk
    tuner = ChunkTuner(perf_p, itl_slo=4.0 * t_p)
    ch = tuner.chunk_for(1, 3, avg_ctx=32.0)
    ch_big = ChunkTuner(perf_p, itl_slo=40.0 * t_p).chunk_for(
        1, 3, avg_ctx=32.0)
    assert ch >= tuner.min_chunk
    assert ch_big >= ch
