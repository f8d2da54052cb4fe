"""Device milliseconds of the window-join Pallas calls per processed
slice, in a replay cell.  The calls are the device ops named after the
``tpu_custom_call`` instructions of the compiled step whose names hold
``window_join``."""


def read(ctx):
    if ctx.mode != "replay" or ctx.trace is None or not ctx.slices_traced:
        return None
    names = {c["name"] for c in ctx.join_calls}
    spent = sum(v for k, v in ctx.trace["op_time"].items() if k in names)
    if spent <= 0:
        return None
    return 1e3 * spent / ctx.slices_traced
