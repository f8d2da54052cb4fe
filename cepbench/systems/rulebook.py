"""The program's served rulebook, as a deployment opens it: the rules of
the configuration's pattern, ``cep.open_rulebook(rules, partitions=K,
monitor=True)``, one keyed ``process`` call per slice, answered
``(K, R + R_kleene)``: each rule's full-match count in the configuration's
order, then each Kleene rule's companion count, as the reference
answers."""

from __future__ import annotations

import os
from typing import Dict

import numpy as np

from cepbench import harness


class Program:
    def __init__(self, config: dict, here: str):
        from repro import cep
        from repro.cep import RuntimeConfig

        if config.get("plan", "order") != "order":
            raise ValueError("the rulebook runs order plans only")
        spec = config["pattern"]
        build = harness._module(os.path.join(here, "patterns",
                                             spec["kind"] + ".py")).build
        self.rulebook = cep.open_rulebook(
            build(spec), partitions=int(config["partitions"]), monitor=True,
            config=RuntimeConfig(**config["runtime"]))
        self.kleene = [i for i, rule in enumerate(spec["rules"])
                       if any(isinstance(e, dict) and "kleene" in e
                              for e in rule["elements"])]

    def process(self, type_id, ts, attr, keys, t0, t1) -> np.ndarray:
        # The rulebook answers (R, K).
        full, closure = self.rulebook.process(type_id, ts, attr, keys, t0,
                                              t1, closure=True)
        return np.concatenate([full, closure[self.kleene]]).T

    def counters(self) -> Dict[str, int]:
        tel = self.rulebook.telemetry()
        return {"replans": tel.replans, "violations": tel.violations,
                "overflow": tel.overflow, "dropped": tel.dropped}


def open(config: dict, here: str) -> Program:
    return Program(config, here)
