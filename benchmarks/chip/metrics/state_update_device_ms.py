"""Device time per window round of the client-state update: the module runs
dispatched innermost under ``fed.state_update`` (the dense scatter of the
cohort's observations and ``update_client_state``, core/state.py); read by
program_spans.py. Silent where the program marks no phases."""

from program_spans import per_round_ms


def read(ctx):
    return per_round_ms(ctx, "device_s", "state_update")
