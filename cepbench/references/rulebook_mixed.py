"""Plain reference of a ``rulebook_mixed`` spec: full-match counts of each
rule of a mixed SEQ / AND / NEG / Kleene rulebook, then the Kleene
companion count of each Kleene rule, one ``(R + R_kleene,)`` vector per
slice of one partition, rules in the spec's order.

A rule is ``{"op", "elements", "where", "window"}``.  ``elements`` are
event types; in a SEQ one of them may be ``{"neg": t}`` (required absence)
or ``{"kleene": t, "bound": b}`` (closure).  A predicate
``{"a": [i, x], "op": "<" | ">", "b": [j, y], "theta": th}`` reads
attribute ``x`` of the event at primitive position ``i`` (a negated
element takes no position) and says ``a < b + th`` or ``a > b - th``.

Semantics (the configuration's guarantee), choice by choice:

* a match is one event per primitive position, of that position's type,
  with every predicate true, strictly: ``a < b + th`` and ``a > b - th``
  are false on equality;
* SEQ: timestamps strictly increasing along the positions; an equal pair
  of timestamps is no match;
* AND: any order, equal timestamps included;
* the window: span = latest - earliest timestamp strictly below
  ``window``; a span of exactly ``window`` is no match;
* NEG ``SEQ(.., p, NEG(t), q, ..)``: a match is dropped where an event of
  type ``t`` lies strictly between its events at ``p`` and ``q``; one at
  the same time as either does not drop it.  Such an event lies inside
  the match's span, so the window holds for it too;
* Kleene ``SEQ(.., KLEENE(t, b), ..)``: the full-match count is that of
  the base matches, one event of type ``t`` at the Kleene position.  The
  rule's companion count, answered after every rule's full count, sums
  over those matches ``min(c - 1, b)``, where ``c`` counts the events of
  type ``t`` that could stand at the Kleene position of the match with
  its other events fixed (its own included, hence the ``- 1``; no bound
  when ``b`` is absent).  The reference takes a Kleene element between
  two primitive positions of a SEQ without a negation only: such a
  companion lies strictly between its neighbours, so inside the span;
* each match is counted once, in the slice ``(t0, t1]`` that holds its
  latest event; its companions are counted with it.

These are the choices of the program's oracle (``core/ref_engine.py``),
whose window is strict like the program's.  Timestamps and attributes
sit on a 2**-10 grid and thresholds are multiples of 2**-10, so every
comparison is exact in float64 here and in float32 on the chip.

SEQ counts are products of 0/1 matrices along the positions (as in
``seq_chain.py``): ``A_i[a, b]`` says that event ``a`` at position ``i``
may precede event ``b`` at position ``i + 1`` (order, the predicates
between the two positions and, across a negation, no negated event in
between); the window and the slice keep the chains whose first and last
events qualify.  SEQ predicates therefore join adjacent positions only.
Around a Kleene position ``k``, ``C = A_{k-1} @ A_k`` counts each pair of
neighbours' compatible events, so the companion count takes the chain
with ``C * min(C - 1, b)`` in place of that product.
AND counts enumerate every combination by broadcasting.  Counts are exact
in float64 up to 2**53.  This module imports nothing of the program.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


class _Rule:
    def __init__(self, rule: dict):
        self.is_seq = rule["op"] == "seq"
        if rule["op"] not in ("seq", "and"):
            raise ValueError(f"unknown rule operator {rule['op']!r}")
        self.window = float(rule["window"])
        self.types: List[int] = []
        self.neg = None  # (position after the negation, negated type)
        self.kleene = None  # (Kleene position, bound or None)
        for el in rule["elements"]:
            if isinstance(el, dict) and "neg" in el:
                self.neg = (len(self.types), int(el["neg"]))
            elif isinstance(el, dict):
                self.kleene = (len(self.types), el.get("bound"))
                self.types.append(int(el["kleene"]))
            else:
                self.types.append(int(el))
        n = len(self.types)
        if self.neg is not None and not 0 < self.neg[0] < n:
            raise ValueError("the reference takes a negation between two "
                             "primitive positions only")
        if self.kleene is not None and not (
                self.is_seq and self.neg is None
                and 0 < self.kleene[0] < n - 1):
            raise ValueError("the reference takes a Kleene element between "
                             "two primitive positions of a SEQ without a "
                             "negation only")
        self.preds = []
        for w in rule["where"]:
            (i, x), (j, y) = w["a"], w["b"]
            if w["op"] not in ("<", ">"):
                raise ValueError(f"unknown predicate {w['op']!r}")
            if self.is_seq and abs(i - j) != 1:
                raise ValueError("the reference takes SEQ predicates "
                                 "between adjacent positions only")
            self.preds.append((int(i), int(x), w["op"], int(j), int(y),
                               float(np.float32(w["theta"]))))

    def _pred(self, p, ev, shape_a, shape_b):
        """Predicate ``p`` over every pair of its two positions' events,
        each side reshaped to broadcast along its own axis."""
        i, ax, op, j, by, th = p
        a = ev.attr(self.types[i])[:, ax].reshape(shape_a)
        b = ev.attr(self.types[j])[:, by].reshape(shape_b)
        return a < b + th if op == "<" else a > b - th

    def count(self, ev: "_Slice") -> Tuple[int, int]:
        """The slice's full-match count and, for a Kleene rule, its
        companion count (0 otherwise)."""
        return self._seq(ev) if self.is_seq else (self._and(ev), 0)

    def _seq(self, ev: "_Slice") -> Tuple[int, int]:
        n = len(self.types)
        ts = [ev.ts(t) for t in self.types]
        if any(len(x) == 0 for x in ts):
            return 0, 0
        steps = []
        for i in range(n - 1):
            step = ev.before(self.types[i], self.types[i + 1])
            for p in self.preds:
                if {p[0], p[3]} == {i, i + 1}:
                    a_first = p[0] == i
                    ok = self._pred(p, ev, (-1, 1) if a_first else (1, -1),
                                    (1, -1) if a_first else (-1, 1))
                    step = step & ok
            if self.neg is not None and self.neg[0] == i + 1:
                step = step & ~ev.between(self.types[i], self.types[i + 1],
                                          self.neg[1])
            steps.append(step.astype(np.float64))
        first, last = ts[0], ts[-1]
        ok = ((last[None, :] - first[:, None] < self.window)
              & (last[None, :] > ev.t0) & (last[None, :] <= ev.t1))

        def total(chain):
            paths = chain[0]
            for step in chain[1:]:
                paths = paths @ step
            return int(round(float((paths * ok).sum())))

        if self.kleene is None:
            return total(steps), 0
        k, bound = self.kleene
        c = steps[k - 1] @ steps[k]
        extra = np.maximum(c - 1, 0)
        if bound is not None:
            extra = np.minimum(extra, float(bound))
        return total(steps), total(steps[:k - 1] + [c * extra]
                                   + steps[k + 1:])

    def _and(self, ev: "_Slice") -> int:
        n = len(self.types)
        ts = [ev.ts(t) for t in self.types]
        if any(len(x) == 0 for x in ts):
            return 0

        def axis(i):
            shape = [1] * n
            shape[i] = -1
            return tuple(shape)

        grid = [ts[i].reshape(axis(i)) for i in range(n)]
        hi, lo = grid[0], grid[0]
        for g in grid[1:]:
            hi, lo = np.maximum(hi, g), np.minimum(lo, g)
        ok = (hi - lo < self.window) & (hi > ev.t0) & (hi <= ev.t1)
        for p in self.preds:
            ok = ok & self._pred(p, ev, axis(p[0]), axis(p[3]))
        return int(np.count_nonzero(ok))


class _Slice:
    """The events a partition holds at one slice, by type, with the
    comparisons that several rules share computed once."""

    def __init__(self, ts, tid, attr, t0: float, t1: float):
        self.t0, self.t1 = t0, t1
        self._ts, self._tid, self._attr = ts, tid, attr
        self._by_type: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        self._before: Dict[Tuple[int, int], np.ndarray] = {}
        self._between: Dict[Tuple[int, int, int], np.ndarray] = {}

    def _type(self, t: int):
        if t not in self._by_type:
            sel = self._tid == t
            self._by_type[t] = (self._ts[sel], self._attr[sel])
        return self._by_type[t]

    def ts(self, t: int) -> np.ndarray:
        return self._type(t)[0]

    def attr(self, t: int) -> np.ndarray:
        return self._type(t)[1]

    def before(self, a: int, b: int) -> np.ndarray:
        """``[i, j]``: event ``i`` of type ``a`` strictly before event
        ``j`` of type ``b``."""
        if (a, b) not in self._before:
            self._before[a, b] = self.ts(a)[:, None] < self.ts(b)[None, :]
        return self._before[a, b]

    def between(self, a: int, b: int, neg: int) -> np.ndarray:
        """``[i, j]``: an event of type ``neg`` lies strictly between event
        ``i`` of type ``a`` and event ``j`` of type ``b``."""
        key = (a, b, neg)
        if key not in self._between:
            nts = np.sort(self.ts(neg))
            after_a = np.searchsorted(nts, self.ts(a), side="right")
            before_b = np.searchsorted(nts, self.ts(b), side="left")
            self._between[key] = before_b[None, :] > after_a[:, None]
        return self._between[key]


class Reference:
    """Stateful per-partition matcher: feed slices in time order."""

    def __init__(self, spec: dict, carry: bool = True):
        self.rules = [_Rule(r) for r in spec["rules"]]
        self.kleene = [i for i, r in enumerate(self.rules)
                       if r.kleene is not None]
        self.horizon = max(r.window for r in self.rules)
        self.n_attrs = int(spec["n_attrs"])
        # carry=False forgets every earlier slice: matches that span a
        # slice edge are lost (the control of the benchmark's check).
        self.carry = carry
        self._ts = np.zeros(0, np.float64)
        self._tid = np.zeros(0, np.int64)
        self._attr = np.zeros((0, self.n_attrs), np.float64)

    def process(self, type_id, ts, attr, t0: float, t1: float) -> np.ndarray:
        ts = np.asarray(ts, np.float64)
        tid = np.asarray(type_id, np.int64)
        attr = np.asarray(attr, np.float64).reshape(len(ts), self.n_attrs)
        if self.carry:
            ts = np.concatenate([self._ts, ts])
            tid = np.concatenate([self._tid, tid])
            attr = np.concatenate([self._attr, attr])
        # A match counted from here on ends after t0 and spans less than
        # the longest window.
        keep = ts > t0 - self.horizon
        self._ts, self._tid, self._attr = ts[keep], tid[keep], attr[keep]
        ev = _Slice(self._ts, self._tid, self._attr, float(t0), float(t1))
        full, comp = zip(*(r.count(ev) for r in self.rules))
        return np.array(list(full) + [comp[i] for i in self.kleene],
                        np.int64)
