"""Cohort execution's share of its roofline: the least time the chip needs
for one round's local training, the larger of its model FLOPs over the peak
bf16 rate and its floor of bytes over the memory bandwidth (flops/), over
the local-train program's device time per round from the trace."""

COHORT_PROGRAM = r"^jit_local_train$"


def read(ctx):
    sec = ctx.module_seconds(COHORT_PROGRAM)
    if not sec:
        return None
    fl = ctx.flops()
    cfg = ctx.cell["config_file"]
    least = max(fl.train_flops(cfg, ctx.cell) / ctx.peak("bf16_flops"),
                fl.train_bytes(cfg, ctx.cell) / ctx.peak("hbm_bytes_per_s"))
    return 100.0 * least / (sec / ctx.trace["n_rounds"])
