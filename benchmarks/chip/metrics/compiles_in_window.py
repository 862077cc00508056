"""Programs compiled or loaded from the compile cache inside the window
(the round engine, fed/engine.py): JAX's backend-compile events, counted by
a jax.monitoring listener the harness registers. A steady window has 0."""


def read(ctx):
    return float(ctx.compiles)
