"""Device idle time per window round while the host is inside the engine's
``fed.eval`` span: the idle gaps of device 0 whose midpoint, on the host's
clock (device times corrected by the measured offset), lies innermost under
``fed.eval``; read by program_spans.py. Silent where the program marks no
phases."""

from program_spans import per_round_ms


def read(ctx):
    return per_round_ms(ctx, "idle_s", "eval")
