"""End-to-end driver (deliverable b): REAL disaggregated serving of a small
model with batched multi-round requests on CPU.

Spins up 1 prefill + 1 decode worker (each a live JAX engine), profiles them
to fit the perf model, then serves multi-round sessions with AMPD's adaptive
routing + reordering: initial prefills remote (KV transferred), incremental
prefills routed adaptively (lazy history reads when remote), continuous-
batching decode with greedy sampling.

Run:  PYTHONPATH=src python examples/serve_multiround.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs import get_config
from repro.core.types import SLOSpec
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import ClusterSpec, LiveCluster, make_live_sessions


def main():
    enable_compile_cache()
    cfg = get_config("qwen2.5-14b").reduced()   # same family, CPU-sized
    print(f"model: {cfg.name} (reduced: {cfg.num_layers}L d={cfg.d_model})")

    cluster = LiveCluster(cfg,
                          spec=ClusterSpec(n_prefill=1, n_decode=1,
                                           max_slots=4, max_len=192),
                          slo=SLOSpec(ttft_thres=5.0, itl_thres=1.0),
                          seed=0)
    print("profiled perf model:",
          f"t_pre(0,64)={cluster.perf.t_pre(0, 64, 1)*1e3:.1f}ms",
          f"t_dec(b=4)={cluster.perf.t_dec(4, 1, 64)*1e3:.1f}ms")

    sessions = make_live_sessions(cfg, num_sessions=4, rounds=3,
                                  prefill_len=24, decode_len=6,
                                  arrival_gap=0.02)
    result = cluster.run_trace(sessions)

    print(f"\nserved {len(sessions)} sessions x 3 rounds "
          f"(logical {result.logical_time:.2f}s, wall {result.wall_time:.1f}s)")
    print(f"SLO attainment: {result.slo_attainment:.2f}  "
          f"avg TTFT {result.avg_ttft*1e3:.0f}ms  "
          f"avg ITL {result.avg_itl*1e3:.0f}ms")
    print(f"adaptive routing: {result.local_fraction:.0%} local, "
          f"KV moved {result.kv_bytes_moved/1e6:.2f} MB")
    for s in sessions[:2]:
        print(f"  session {s.session_id}: rounds={len(s.ttfts)} "
              f"generated={len(s.generated)} tokens "
              f"first-10={s.generated[:10]}")


if __name__ == "__main__":
    main()
