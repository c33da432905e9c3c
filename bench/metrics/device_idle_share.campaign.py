"""Share of the campaign window in which no operation ran on the device:
1 - (union of device operation intervals) / window, averaged over chips."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    return 100.0 * ctx.trace.idle_share()
