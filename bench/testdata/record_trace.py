"""Record the small trace that ``bench/tests/test_bench_trace.py`` reads.

    python3 bench/testdata/record_trace.py

Run on a TPU host from the root of a checkout.  It serves a tiny decoder
through the program's ``Engine`` (a dense prefill, a dense decode step and
a packed step, so the three attention kernels and both step programs
appear) under the benchmark's own host spans, and writes
``engine_steps.xplane.pb`` beside this file.  The traced decode step runs
four rows holding 41, 2, 1 and 1 cached tokens (the warm-up's decode step
and packed step wrote them).
"""
import glob
import os
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent

if __name__ == "__main__":
    sys.path[:0] = [str(HERE.parents[1]), str(HERE.parents[1] / "src")]
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.model import make_params, model_config
    from repro.serving.engine import Engine

    assert jax.devices()[0].platform == "tpu", "record on a TPU"
    c = {"name": "tiny", "hidden_size": 256, "intermediate_size": 512,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "num_hidden_layers": 2, "vocab_size": 1024, "rms_norm_eps": 1e-5,
         "rope_theta": 1e4, "tie_word_embeddings": False,
         "attention_bias": True, "reference": "decoder"}
    eng = Engine(model_config(c), max_len=256, params=make_params(c, 1))
    cache = eng.new_cache(4)
    ids = np.arange(64, dtype=np.int32) % 1024

    def steps(cache):
        with jax.profiler.TraceAnnotation("bench:prefill"):
            c1, logits = eng.prefill(ids)
            jax.block_until_ready(logits)
        with jax.profiler.TraceAnnotation("bench:decode"):
            cache, logits = eng.decode_step(cache, jnp.ones((4, 1), jnp.int32))
            jax.block_until_ready(logits)
        with jax.profiler.TraceAnnotation("bench:fused"):
            cache, logits, _ = eng.run_packed(cache, [(0, ids[:40]),
                                                      (1, ids[:1])])
            jax.block_until_ready(logits)
        return cache

    cache = steps(cache)                  # compile outside the trace
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench:window"):
        cache = steps(cache)
    jax.profiler.stop_trace()
    src = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                           recursive=True))[-1]
    shutil.copy(src, HERE / "engine_steps.xplane.pb")
    shutil.rmtree(tmp)
    print(os.path.getsize(HERE / "engine_steps.xplane.pb"))
