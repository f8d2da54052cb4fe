"""Device milliseconds per processed slice of the ops in the rulebook
plane's ``cep.negate`` and ``cep.kleene`` scopes, in a replay cell: the
negation veto and the Kleene companion count of the per-rule post-blocks
(``core/multipattern.py::_rule_negate``, ``_rule_kleene``)."""

from cepbench import spans

# The scope names, as the program writes them.
SCOPES = ("cep.negate", "cep.kleene")


def read(ctx):
    if ctx.mode != "replay":
        return None
    return spans.scope_ms_per_slice(ctx, SCOPES)
