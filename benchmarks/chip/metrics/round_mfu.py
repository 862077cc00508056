"""The whole round's share of the chips' peak: model FLOPs per round (the
cohort's forward and backward passes plus the eval forward, no recompute;
flops/) over the traced window's round time, the chips and the peak bf16
rate."""


def read(ctx):
    fl = ctx.flops()
    cfg = ctx.cell["config_file"]
    model = fl.train_flops(cfg, ctx.cell) + fl.eval_flops(cfg, ctx.cell)
    sec = ctx.trace["window_s"] / ctx.trace["n_rounds"]
    return 100.0 * model / (sec * ctx.cell["chips"] * ctx.peak("bf16_flops"))
