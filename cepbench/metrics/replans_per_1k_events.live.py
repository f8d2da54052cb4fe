"""Invariant-triggered replans of the control plane per 1,000 events of
the window, in a live cell (``Telemetry.replans``)."""


def read(ctx):
    if ctx.mode != "live" or not ctx.window["events"]:
        return None
    return 1e3 * ctx.counters["replans"] / ctx.window["events"]
