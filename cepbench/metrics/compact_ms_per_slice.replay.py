"""Device milliseconds per processed slice of the ops in the compiled
step's ``cep.compact`` scope, in a replay cell: the prefix-sum compaction
of each join's surviving pairs into the next match set
(``core/engine.py::_compact``)."""

from cepbench import spans


def read(ctx):
    if ctx.mode != "replay":
        return None
    return spans.scope_ms_per_slice(ctx, [spans.COMPACT])
