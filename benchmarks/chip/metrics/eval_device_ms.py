"""Device time per window round of the programs the eval dispatches: the
module runs whose host dispatch lies innermost under the engine's
``fed.eval`` span (fed/engine.py ``default_eval``, eager ops included),
read by program_spans.py. Silent where the program marks no phases."""

from program_spans import per_round_ms


def read(ctx):
    return per_round_ms(ctx, "device_s", "eval")
