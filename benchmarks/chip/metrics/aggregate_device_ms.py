"""Device time per window round of aggregation: the module runs dispatched
innermost under any ``fed.aggregate`` span — the fused FedAvg and each
chunk's weighted sum inside cohort execution (fed/batched.py), and the
aggregator's reduce in the engine (fed/server.py); read by
program_spans.py. Silent where the program marks no phases."""

from program_spans import per_round_ms


def read(ctx):
    return per_round_ms(ctx, "device_s", "aggregate")
