"""Names of the program's trace spans and device scopes.

Host spans are ``jax.profiler.TraceAnnotation``s: TraceMe events on the
profiler's host clock, the same clock as the device's ``XLA Ops`` line.
Without an active profiler session each one costs about a microsecond and
records nothing, so there is no switch.  Device scopes are
``jax.named_scope``s inside the compiled step; they change only the
``op_name`` metadata of the HLO, through which a device trace names the
layer each op belongs to.

The span tree of one served slice (``Session.process`` and
``Rulebook.process``), each span carrying the slice index as its
``chunk`` argument::

    cep.process
      cep.route       keyed batch -> stacked per-partition chunk
      cep.step        dispatch of the compiled step (one per rulebook
                      bucket)
      cep.readback    each blocking device->host read of the slice
      cep.control     the flag follow-up, entered on every slice (every
                      bucket of a rulebook)
        cep.readback  a rulebook bucket's one statistics pull
        cep.replan    one per flagged partition (argument ``partition``;
                      a rulebook's also ``rule``, the flagged rule's id)
          cep.readback  a session's ``rates[p]`` and ``sel[p]`` pulls

The rulebook plane writes the session step's device scopes, and two of
its own inside ``cep.finalize``: ``cep.negate`` (the negation veto) and
``cep.kleene`` (the Kleene companion count).
"""

# Host spans.
PROCESS = "cep.process"
ROUTE = "cep.route"
STEP = "cep.step"
READBACK = "cep.readback"
CONTROL = "cep.control"
REPLAN = "cep.replan"

# Device scopes of the compiled step.
INGEST = "cep.ingest"
JOIN = "cep.join"
COMPACT = "cep.compact"
FINALIZE = "cep.finalize"
MONITOR = "cep.monitor"
VERIFY = "cep.verify"
NEGATE = "cep.negate"
KLEENE = "cep.kleene"
