"""Share of the traced window in which the device ran no operation, in a
live cell: 1 - (union of device-op intervals) / window."""


def read(ctx):
    if ctx.mode != "live" or ctx.trace is None:
        return None
    t = ctx.trace
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
