"""Host milliseconds per processed slice in the program's ``cep.route``
spans, in a replay cell: routing one keyed batch into the stacked
per-partition chunk and its four host-to-device puts
(``CEPFleetServingEngine.route``)."""

from cepbench import spans


def read(ctx):
    if ctx.mode != "replay":
        return None
    return spans.host_ms_per_slice(ctx, spans.ROUTE)
