"""Share of the device's busy time spent outside the sweep kernel (the
executor's pad, crop, cast and zero-fill), averaged over chips.  The sweep
kernel is the cell's Mosaic custom call (``profile_reduce.is_mosaic``)."""


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    shares = []
    for dev in tr.devices:
        busy, kernel = tr.busy_s(dev), tr.kernel_s(dev)
        if busy > 0 and kernel > 0:
            shares.append(100.0 * (busy - kernel) / busy)
    return sum(shares) / len(shares) if shares else None
