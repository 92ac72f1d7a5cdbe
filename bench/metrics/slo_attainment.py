"""Share of the rounds due in the window that met the SLO: first token
within the round's deadline (TTFT for round 0, TTIT after), every output
token emitted by the drain's end, and a mean gap within the ITL limit."""


def read(run):
    if not run.rounds:
        return None
    slo = run.slo
    met = 0
    for r in run.rounds:
        if r.first is None or len(r.times) < r.output:
            continue
        deadline = slo["ttft_s"] if r.index == 0 else slo["ttit_s"]
        gaps = [b - a for a, b in zip(r.times, r.times[1:])]
        mean_gap = sum(gaps) / len(gaps) if gaps else 0.0
        if r.first - r.due <= deadline and mean_gap <= slo["itl_s"]:
            met += 1
    return met / len(run.rounds)
