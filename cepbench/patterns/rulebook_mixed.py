"""The program's patterns for a ``rulebook_mixed`` spec: one DSL pattern
per rule, in the spec's order (the spec's format is in
``references/rulebook_mixed.py``)."""

from __future__ import annotations


def build(spec: dict) -> list:
    from repro.cep import P

    def element(el):
        if isinstance(el, dict) and "neg" in el:
            return P.neg(int(el["neg"]))
        if isinstance(el, dict):
            return P.kleene(int(el["kleene"]), el.get("bound"))
        return int(el)

    def predicate(w: dict):
        a, b, th = P.attr(*w["a"]), P.attr(*w["b"]), float(w["theta"])
        if w["op"] == "<":
            return a < b + th
        if w["op"] == ">":
            return a > b - th
        raise ValueError(f"unknown predicate {w['op']!r}")

    combine = {"seq": P.seq, "and": P.and_}
    return [combine[rule["op"]](*(element(e) for e in rule["elements"]))
            .where(*(predicate(w) for w in rule["where"]))
            .within(float(rule["window"])).attrs(int(spec["n_attrs"]))
            for rule in spec["rules"]]
