"""Plain reference of a SEQ pattern with chained predicates.

Semantics (the configuration's guarantee): a match is a sequence of
events ``e_0 .. e_{n-1}`` of the types ``types[0] .. types[n-1]`` with
strictly increasing timestamps, a span ``ts(e_{n-1}) - ts(e_0)`` below
``window``, and ``attr(e_i) < attr(e_{i+1}) + theta`` for each adjacent
pair.  Every match is counted exactly once per partition, in the slice
``(t0, t1]`` that holds its latest event.

The count of one slice is a product of 0/1 matrices: ``A_i[a, b]`` says
that event ``a`` of position ``i`` may precede event ``b`` of position
``i + 1``; the entries of ``A_0 A_1 ... A_{n-2}`` count the chains
between each first and last event, and the window and the slice keep
those whose first and last events qualify.  Counts are exact in float64
up to 2**53.  This module imports nothing of the program.
"""

from __future__ import annotations

import numpy as np


class Reference:
    """Stateful per-partition matcher: feed slices in time order."""

    def __init__(self, spec: dict, carry: bool = True):
        self.types = [int(t) for t in spec["types"]]
        self.window = float(spec["window"])
        self.theta = float(np.float32(spec["theta"]))
        self.attr = int(spec.get("attr", 0))
        # carry=False forgets every earlier slice: matches that span a
        # slice edge are lost (the control of the benchmark's check).
        self.carry = carry
        self._ts = np.zeros(0, np.float64)
        self._tid = np.zeros(0, np.int64)
        self._val = np.zeros(0, np.float64)

    def process(self, type_id, ts, attr, t0: float, t1: float) -> int:
        ts = np.asarray(ts, np.float64)
        val = np.asarray(attr, np.float64)[:, self.attr]
        tid = np.asarray(type_id, np.int64)
        if self.carry:
            ts = np.concatenate([self._ts, ts])
            tid = np.concatenate([self._tid, tid])
            val = np.concatenate([self._val, val])
        keep = ts > t0 - self.window
        self._ts, self._tid, self._val = ts[keep], tid[keep], val[keep]
        return self._count(t0, t1)

    def _count(self, t0: float, t1: float) -> int:
        pos = [np.nonzero(self._tid == t)[0] for t in self.types]
        if any(len(p) == 0 for p in pos):
            return 0
        ts = [self._ts[p] for p in pos]
        val = [self._val[p] for p in pos]
        paths = np.eye(len(pos[0]))
        for i in range(len(pos) - 1):
            step = ((ts[i][:, None] < ts[i + 1][None, :])
                    & (val[i][:, None] < val[i + 1][None, :] + self.theta))
            paths = paths @ step.astype(np.float64)
        first, last = ts[0], ts[-1]
        ok = ((last[None, :] - first[:, None] < self.window)
              & (last[None, :] > t0) & (last[None, :] <= t1))
        return int(round(float((paths * ok).sum())))
