"""Blocking device-to-host reads per 1,000 events of the window, in a
replay cell: the program's ``cep.readback`` spans, one around each read
that ``Telemetry.readbacks`` counts (a slice's counters, its flags and
drift, and ``rates[p]`` and ``sel[p]`` of each flagged partition)."""

from cepbench import spans


def read(ctx):
    if ctx.mode != "replay" or not ctx.window["events"]:
        return None
    got = spans.host_span(ctx, spans.READBACK)
    return None if got is None else 1e3 * got[1] / ctx.window["events"]
