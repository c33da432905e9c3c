"""Share of HBM bandwidth the 3-D sweep kernel uses on each chip of a
sharded cell, on the bytes every sweep must move for the chip's shard:
launches x 2 x shard cells (the domain over the mesh) x bytes per cell,
over the kernel's device time on that chip x the chip's published HBM
rate, averaged over chips.  The window's redundant cells beyond the shard
are charged in time, not counted as bytes.  The kernel is the cell's
Mosaic custom call (``profile_reduce.is_mosaic``); the reader applies
only to a 3-D configuration with a mesh, and finds nothing where a chip
has no launch."""
import math

BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def read(ctx):
    tr = ctx.trace
    mesh, domain = ctx.config.get("mesh"), ctx.config["domain"]
    if tr is None or not tr.devices or not mesh or len(domain) != 3:
        return None
    shard = math.prod(domain) // math.prod(mesh)
    shares = []
    for dev in tr.devices:
        launches = len(tr.kernel_ops(dev))
        kernel_s = tr.kernel_s(dev)
        if launches == 0 or kernel_s <= 0:
            return None
        moved = launches * 2 * shard * BYTES[ctx.config["dtype"]]
        shares.append(100.0 * moved
                      / (kernel_s * ctx.peaks["hbm_bytes_per_s"]))
    return sum(shares) / len(shares)
