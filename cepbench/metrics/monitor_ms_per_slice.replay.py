"""Device milliseconds per processed slice of the ops in the compiled
step's ``cep.monitor`` and ``cep.verify`` scopes, in a replay cell: the
statistics observation, the ring update and snapshot, and the evaluation
of the lowered invariants (``make_monitored_process``)."""

from cepbench import spans


def read(ctx):
    if ctx.mode != "replay":
        return None
    return spans.scope_ms_per_slice(ctx, [spans.MONITOR, spans.VERIFY])
