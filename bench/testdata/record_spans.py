"""Record the trace with program spans that ``bench/tests/test_bench_spans.py``
reads.

    python3 bench/testdata/record_spans.py

Run on a TPU host from the root of a checkout.  It serves a tiny decoder
through the program's own workers and backend: a first prefill on the
prefill worker, its increment joining a decode slot, a dense decode step
over four rows, a later round's prefill that reads its history off the
decode slot, and a local (packed) prefill on the decode worker.  So the
program's ``repro.*`` spans land in the trace beside the device's
programs, and ``engine_steps_spans.xplane.pb`` is written beside this file.
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
    import numpy as np

    from bench.model import make_params, model_config
    from repro.core.perf_model import PerfModel
    from repro.core.types import PrefillTask, RoundSpec
    from repro.runtime.backend import LiveBackend
    from repro.serving.engine import Engine
    from repro.serving.workers import (LiveDecodeWorker, LivePrefillWorker,
                                       LiveSession)

    assert jax.devices()[0].platform == "tpu", "record on a TPU"
    c = {"name": "tiny", "hidden_size": 256, "intermediate_size": 512,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "num_hidden_layers": 2, "vocab_size": 1024, "rms_norm_eps": 1e-5,
         "rope_theta": 1e4, "tie_word_embeddings": False,
         "attention_bias": True, "reference": "decoder"}
    cfg = model_config(c)
    params = make_params(c, 1)
    be = LiveBackend(PerfModel(cfg))
    pw = LivePrefillWorker(0, Engine(cfg, max_len=256, params=params))
    dw = LiveDecodeWorker(0, Engine(cfg, max_len=256, params=params),
                          max_slots=4)
    ids = np.arange(128, dtype=np.int32) % 1024

    def steps():
        live = [LiveSession(k, 0.0, [RoundSpec(64, 1, 0.0),
                                     RoundSpec(32, 1, 0.0)],
                            [ids[:64], ids[64:96]]) for k in range(4)]
        for k, s in enumerate(live):
            t = PrefillTask(k, 0, 0, 64, 0.0, 0.0, is_initial=True)
            _, payload = be.run_prefill(pw, t, s, dw)
            be.on_join(dw, s, t, payload)
            s.context_len = 64
        dw.decode_once()
        s = live[0]
        s.context_len = 65
        t = PrefillTask(0, 1, 65, 32, 0.0, 0.0)
        _, payload = be.run_prefill(pw, t, s, dw)
        be.on_join(dw, s, t, payload)
        for s in live[1:]:
            dw.detach(s)
        s = LiveSession(9, 0.0, [RoundSpec(40, 1, 0.0)], [ids[:40]])
        dw.allocate(s)
        dw.local_prefill(PrefillTask(9, 0, 0, 40, 0.0, 0.0,
                                     is_initial=True), s)
        for s in list(dw.slots):
            if s is not None:
                dw.detach(s)

    steps()                               # compile outside the trace
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench:window"):
        steps()
    jax.profiler.stop_trace()
    src = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                           recursive=True))[-1]
    shutil.copy(src, HERE / "engine_steps_spans.xplane.pb")
    shutil.rmtree(tmp)
    print(os.path.getsize(HERE / "engine_steps_spans.xplane.pb"))
