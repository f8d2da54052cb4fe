"""The program's served session, as a deployment opens it: one pattern,
``cep.open(pattern, partitions=K, plan=..., monitor=True)``, one
``process`` call per slice, one count per partition."""

from __future__ import annotations

import os
from typing import Dict

import numpy as np

from cepbench import harness


class Program:
    """The program's served session, as a deployment opens it."""

    def __init__(self, config: dict, here: str):
        from repro import cep
        from repro.cep import RuntimeConfig

        spec = config["pattern"]
        build = harness._module(os.path.join(here, "patterns",
                                             spec["kind"] + ".py")).build
        self.session = cep.open(
            build(spec), partitions=int(config["partitions"]),
            plan=config["plan"], monitor=True,
            config=RuntimeConfig(**config["runtime"]))

    def process(self, type_id, ts, attr, keys, t0, t1) -> np.ndarray:
        return self.session.process(type_id, ts, attr, keys, t0, t1)

    def counters(self) -> Dict[str, int]:
        tel = self.session.telemetry()
        return {"replans": tel.replans, "violations": tel.violations,
                "overflow": tel.overflow, "dropped": tel.dropped}


def open(config: dict, here: str) -> Program:
    return Program(config, here)
