"""Plain reference of a ``seq_rules`` spec: the ``seq_chain`` reference of
each rule, answering one count per rule, in the spec's order."""

from __future__ import annotations

import os

import numpy as np

from cepbench import harness


class Reference:
    def __init__(self, spec: dict, carry: bool = True):
        chain = harness._module(os.path.join(os.path.dirname(__file__),
                                             "seq_chain.py")).Reference
        self.rules = [chain(rule, carry=carry) for rule in spec["rules"]]

    def process(self, type_id, ts, attr, t0: float, t1: float) -> np.ndarray:
        return np.array([r.process(type_id, ts, attr, t0, t1)
                         for r in self.rules], np.int64)
