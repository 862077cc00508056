"""Device time per round of the vmapped local-train program(s) of cohort
execution (fed/batched.py, fed/client.py, the model), summed over the
program's calls in the window's trace and divided by its rounds."""

COHORT_PROGRAM = r"^jit_local_train$"


def read(ctx):
    sec = ctx.module_seconds(COHORT_PROGRAM)
    if not sec:
        return None
    return sec / ctx.trace["n_rounds"] * 1e3
