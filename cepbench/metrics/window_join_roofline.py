"""Share of its roofline that the window-join kernel reaches: the least
time the chip could take for the traced calls over the time they took.

The least time of a call is the larger of its bytes over the peak HBM
bandwidth and its operations over the peak rate (``roofline.py``), from
C, M, B and the batch in the call's operand shapes in the compiled
step's HLO; it is summed over the traced calls of each instruction."""

from cepbench import roofline


def read(ctx):
    if ctx.trace is None or not ctx.join_calls:
        return None
    least, spent, bounds = 0.0, 0.0, {}
    for call in ctx.join_calls:
        n = ctx.trace["op_count"].get(call["name"], 0)
        if not n:
            continue
        t, bound = roofline.least_time(call, ctx.peak)
        least += n * t
        spent += ctx.trace["op_time"][call["name"]]
        bounds[bound] = bounds.get(bound, 0) + n
    if spent <= 0:
        return None
    ctx.log(f"[window_join_roofline] least={least:.6f}s spent={spent:.6f}s "
            f"calls by bound={bounds}")
    return 100.0 * least / spent
