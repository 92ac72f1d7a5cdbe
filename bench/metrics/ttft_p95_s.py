"""95th percentile of time to first token over the rounds due in the window.

Each round is timed from when it was due: round 0 from its scheduled
arrival, round k from round k-1's last token plus its environment delay.
A round with no first token by the end of the drain counts as infinite, so
a p95 that lands on one is infinite and the run reports no result."""
from bench.harness import percentile


def read(run):
    vals = [(r.first - r.due) if r.first is not None else float("inf")
            for r in run.rounds]
    return percentile(vals, 0.95) if vals else None
