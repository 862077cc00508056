"""The fused score_select kernels' share of their roofline: the bytes both
passes must move for K clients' state (flops/score_select.py) over their
device time per round in the trace times the memory bandwidth. Silent
where the kernels do not run."""

from flops import score_select

# The fused kernels run as Mosaic custom calls inside the selection program,
# which the engine jits from a functools.partial (hence "_unknown").
KERNEL_OPS = r"^jit__unknown .*custom-call\(.*tpu_custom_call"


def read(ctx):
    sec = ctx.op_seconds(KERNEL_OPS)
    if not sec:
        return None
    itemsize = 2 if ctx.cell["spec"].get("compact_state") else 4
    need = score_select.kernel_bytes(ctx.cell["traffic"]["num_clients"], itemsize)
    per_round = sec / ctx.trace["n_rounds"]
    return 100.0 * need / (per_round * ctx.peak("hbm_bytes_per_s"))
