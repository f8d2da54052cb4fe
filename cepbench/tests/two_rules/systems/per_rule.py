"""A served system of several rules: one ``cep.open`` session per rule of
the configuration's pattern, their per-partition counts stacked into a
``(K, R)`` answer."""

from __future__ import annotations

import os
from typing import Dict

import numpy as np

from cepbench import harness


class PerRule:
    def __init__(self, config: dict, here: str):
        from repro import cep
        from repro.cep import RuntimeConfig

        spec = config["pattern"]
        build = harness._module(os.path.join(here, "patterns",
                                             spec["kind"] + ".py")).build
        self.sessions = [
            cep.open(pattern, partitions=int(config["partitions"]),
                     plan=config["plan"], monitor=True,
                     config=RuntimeConfig(**config["runtime"]))
            for pattern in build(spec)]

    def process(self, type_id, ts, attr, keys, t0, t1) -> np.ndarray:
        return np.stack([np.asarray(s.process(type_id, ts, attr, keys, t0,
                                              t1))
                         for s in self.sessions], axis=1)

    def counters(self) -> Dict[str, int]:
        tels = [s.telemetry() for s in self.sessions]
        return {key: sum(getattr(t, key) for t in tels)
                for key in ("replans", "violations", "overflow", "dropped")}


def open(config: dict, here: str) -> PerRule:
    return PerRule(config, here)
