"""The SSD scan's share of its roofline in local training: the least time
the chip needs for one round's scan, the larger of its model FLOPs over the
peak bf16 rate and its byte floor over the memory bandwidth (flops/ssd.py),
over the scan's device time per round (ssd_device_ms). Silent where that is."""

from flops import ssd
from metrics import ssd_device_ms


def read(ctx):
    ms = ssd_device_ms.read(ctx)
    if ms is None:
        return None
    cfg = ctx.cell["config_file"]
    least = max(ssd.train_flops(cfg, ctx.cell) / ctx.peak("bf16_flops"),
                ssd.train_bytes(cfg, ctx.cell) / ctx.peak("hbm_bytes_per_s"))
    return 100.0 * least / (ms / 1e3)
