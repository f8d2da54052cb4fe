"""Host milliseconds per processed slice in the program's ``cep.control``
spans, in a live cell: the follow-up of the invariant flags, which the
detection latency of the slice waits for."""

from cepbench import spans


def read(ctx):
    if ctx.mode != "live":
        return None
    return spans.host_ms_per_slice(ctx, spans.CONTROL)
