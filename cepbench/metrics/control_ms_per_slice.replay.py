"""Host milliseconds per processed slice in the program's ``cep.control``
spans, in a replay cell: the follow-up of the invariant flags, with the
statistics pulls, the planner, the policy rebase and the row writes of
each replan (``MonitoredCEPFleetServingEngine._apply_flags``)."""

from cepbench import spans


def read(ctx):
    if ctx.mode != "replay":
        return None
    return spans.host_ms_per_slice(ctx, spans.CONTROL)
