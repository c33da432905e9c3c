"""Share of HBM bandwidth the 3-D sweep kernel uses on the bytes every sweep
must move: each launch reads the domain once and writes it once, so
launches x 2 x domain cells x bytes per cell, over the kernel's device time
x the chip's published HBM rate.  Tile, padding and depth do not count: a
kernel that moves more than this is charged for it in time.  The kernel is
the cell's Mosaic custom call (``profile_reduce.is_mosaic``), and the
reader applies only to a 3-D configuration on one chip."""
import math

BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def read(ctx):
    tr = ctx.trace
    if tr is None or len(ctx.config["domain"]) != 3 or len(tr.devices) != 1:
        return None
    (dev,) = tr.devices
    launches = len(tr.kernel_ops(dev))
    kernel_s = tr.kernel_s(dev)
    if launches == 0 or kernel_s <= 0:
        return None
    moved = launches * 2 * math.prod(ctx.config["domain"]) \
        * BYTES[ctx.config["dtype"]]
    return 100.0 * moved / (kernel_s * ctx.peaks["hbm_bytes_per_s"])
