"""Host time per window round spent building the cohort's batches: the
union of the program's ``fed.gather`` spans (fed/batched.py
``gather_stacked_batches``, or each client's batches under the sequential
executor), in which the device waits for its inputs; read by
program_spans.py. Silent where the program marks no phases."""

from program_spans import per_round_ms


def read(ctx):
    return per_round_ms(ctx, "phases_host_s", "gather")
