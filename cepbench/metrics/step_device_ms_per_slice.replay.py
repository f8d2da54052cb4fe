"""Device-busy milliseconds per processed slice over the traced window of
a replay cell: the compiled step and every other device op."""


def read(ctx):
    if ctx.mode != "replay" or ctx.trace is None or not ctx.slices_traced:
        return None
    return 1e3 * ctx.trace["busy_s"] / ctx.slices_traced
