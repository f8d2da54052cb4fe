"""The program's patterns for a ``seq_rules`` spec: one ``seq_chain``
pattern per rule, in the spec's order."""

from __future__ import annotations

import os

from cepbench import harness


def build(spec: dict) -> list:
    chain = harness._module(os.path.join(os.path.dirname(__file__),
                                         "seq_chain.py")).build
    return [chain(rule) for rule in spec["rules"]]
