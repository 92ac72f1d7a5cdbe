"""95th percentile of the gaps between consecutive output tokens of a
round, over every gap whose later token was emitted in the window."""
from bench.harness import percentile


def read(run):
    gaps = [b - a for r in run.all_rounds
            for a, b in zip(r.times, r.times[1:]) if run.w0 <= b < run.w1]
    return 1e3 * percentile(gaps, 0.95) if gaps else None
