"""Mean wall time of the selection phase per window round: the engine's
own ``ctx.select_ms`` span (core/selection.py, kernels/score_select.py),
which ends in the device sync of the cohort mask."""


def read(ctx):
    vals = [r["select_ms"] for r in ctx.rounds]
    return sum(vals) / len(vals) if vals else None
