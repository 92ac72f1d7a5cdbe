"""KV bytes the prefill workers moved in the window (history reads and
increments, program counter ``kv_bytes_moved``), in MB per round completed
in the window."""


def read(run):
    done = sum(1 for r in run.all_rounds
               if len(r.times) == r.output and run.w0 <= r.times[-1] < run.w1)
    return run.delta("kv_bytes_moved") / 1e6 / done if done else None
