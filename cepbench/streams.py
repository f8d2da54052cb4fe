"""Seeded event streams of the paper's two data regimes (arXiv:1801.08588
§5.1), frozen with the benchmark so that no change to the program moves
the yardstick.

The emission kernel and the two regimes follow ``emit_chunk``,
``traffic_stream`` and ``stocks_stream`` of the program's
``data/cep_streams.py``, with three changes that a benchmark needs:

* shocks of the traffic regime come on a fixed schedule (every
  ``shift_period`` slices, spread evenly over the partitions) instead of
  at exponential times, so every window sees the same amount of drift
  whatever its seed;
* a shock is drawn afresh from the base rates and attribute means, not
  added to the state the last shock left, so rates and attributes stay
  within the same bounds however long the stream runs;
* timestamps and attributes are quantized to a power-of-two grid
  (``quantum``), so every comparison the matcher makes (time order, the
  window, ``a < b + theta``) has the same answer in float32 on the chip
  and in exact arithmetic in the reference, whichever form a plan
  evaluates it in;
* partitions are merged into one keyed batch per slice, with integer
  keys that spread uniformly over the partitions (``key % K``).

Everything is a pure function of the seed.
"""

from __future__ import annotations

import dataclasses
from typing import List, NamedTuple

import numpy as np

class Slice(NamedTuple):
    """Events of one slice of one partition."""

    type_id: np.ndarray
    ts: np.ndarray
    attr: np.ndarray

# Keys are partition + K * salt, salt < 2**20.
_KEY_SALT = 1 << 20


def partition_rng(seed: int, partition: int) -> np.random.Generator:
    """Independent generator of one partition's stream."""
    return np.random.default_rng(np.random.SeedSequence([int(seed),
                                                         int(partition)]))


def quantize(x: np.ndarray, quantum: float) -> np.ndarray:
    return (np.round(np.asarray(x, np.float64) / quantum)
            * quantum).astype(np.float32)


def emit_slice(rng, rates, attr_mean, t0: float, *, slice_s: float,
               quantum: float, fixed_total: bool = False,
               attr_sigma: float = 1.0, attr_clip: float = 64.0) -> Slice:
    """Arrivals at per-type ``rates`` over ``(t0, t0 + slice_s]``.

    Per-type Poisson counts, or, with ``fixed_total``, the slice's expected
    total (rounded) split over the types in proportion to their rates, so
    that every seed brings the same number of events; uniform timestamps
    within the slice (on the grid, strictly after ``t0``), types
    interleaved over time, attributes Gaussian around ``attr_mean``.
    Attributes are clipped to ``attr_clip``: below 64 the float32 rounding
    of ``b + theta`` stays far smaller than the distance from any grid
    point to ``theta = -0.3``.
    """
    expected = np.asarray(rates, np.float64) * slice_s
    if fixed_total:
        counts = rng.multinomial(int(round(expected.sum())),
                                 expected / expected.sum())
    else:
        counts = rng.poisson(expected)
    total = int(counts.sum())
    type_id = np.repeat(np.arange(len(rates), dtype=np.int32), counts)
    type_id = type_id[rng.permutation(total)]
    steps = int(round(slice_s / quantum))
    ts = np.sort(t0 + rng.integers(1, steps + 1, total) * quantum)
    attr = np.asarray(attr_mean, np.float64)[type_id] \
        + rng.normal(0.0, attr_sigma, (total, np.shape(attr_mean)[1]))
    attr = np.clip(attr, -attr_clip, attr_clip)
    return Slice(type_id, ts.astype(np.float32), quantize(attr, quantum))


def _profile_rng(rng, mix: dict, part: int):
    """The generator of a partition's rates and attribute means: the mix's
    ``profile_seed`` where it names one, else the run's own ``rng``."""
    if "profile_seed" not in mix:
        return rng
    return partition_rng(mix["profile_seed"], part)


def traffic_partition(rng, mix: dict, n_types: int, n_attrs: int,
                      n_slices: int, part: int, parts: int) -> List[Slice]:
    """High skew, stable rates with rare extreme shocks (Aarhus-like).

    Zipf-skewed base rates summing to ``base_rate`` and base attribute
    means around 0.  Where ``shift_period`` > 0, partition ``part`` of
    ``parts`` is shocked at every slice ``s`` > 0 with
    ``(s + part * shift_period // parts) % shift_period == 0``: from the
    base rates, two types swap rates, one scaled up and one down by
    ``shift_magnitude``, and one type's attribute mean moves from its base
    by N(0, ``attr_shift_sigma``).  The shocked values hold until the
    next shock.

    Where the mix names a ``profile_seed``, the base values and the shocks
    of each partition are drawn from it and the partition's index, not
    from ``rng``: every seed then brings the same partitions and the same
    drift, and the seed draws the arrivals and attribute values alone.
    """
    prof = _profile_rng(rng, mix, part)
    raw = 1.0 / np.arange(1, n_types + 1, dtype=np.float64) ** mix["zipf_s"]
    prof.shuffle(raw)
    base_rates = raw / raw.sum() * mix["base_rate"]
    base_attr = prof.normal(0.0, 1.0, (n_types, n_attrs))
    rates, attr_mean = base_rates, base_attr
    period = int(mix.get("shift_period", 0))
    stagger = part * period // parts
    out = []
    for s in range(n_slices):
        if period and s > 0 and (s + stagger) % period == 0:
            i, j = prof.choice(n_types, 2, replace=False)
            mag = mix["shift_magnitude"]
            rates = base_rates.copy()
            rates[i], rates[j] = base_rates[j] * mag, base_rates[i] / mag
            rates = rates / rates.sum() * mix["base_rate"]
            attr_mean = base_attr.copy()
            attr_mean[prof.integers(n_types)] += prof.normal(
                0.0, mix["attr_shift_sigma"], n_attrs)
        out.append(emit_slice(rng, rates, attr_mean, s * mix["slice_s"],
                              slice_s=mix["slice_s"],
                              quantum=mix["quantum"],
                              fixed_total=mix.get("fixed_total", False)))
    return out


def stocks_partition(rng, mix: dict, n_types: int, n_attrs: int,
                     n_slices: int, part: int, parts: int) -> List[Slice]:
    """Near-uniform rates with frequent small random-walk drift
    (NASDAQ-like).  A ``profile_seed`` fixes the rates and attribute means
    of every slice as in ``traffic_partition``."""
    del parts
    prof = _profile_rng(rng, mix, part)
    mean_log = np.log(mix["base_rate"] / n_types)
    log_rates = np.full(n_types, mean_log) + prof.normal(0.0, 0.01, n_types)
    attr_mean = prof.normal(0.0, 0.1, (n_types, n_attrs))
    out = []
    for s in range(n_slices):
        log_rates += prof.normal(0.0, mix["walk_sigma"], n_types)
        log_rates -= (log_rates.mean() - mean_log) * 0.05
        attr_mean += prof.normal(0.0, mix["attr_walk_sigma"],
                                 (n_types, n_attrs))
        out.append(emit_slice(rng, np.exp(log_rates), attr_mean,
                              s * mix["slice_s"], slice_s=mix["slice_s"],
                              quantum=mix["quantum"],
                              fixed_total=mix.get("fixed_total", False)))
    return out


REGIMES = {"traffic": traffic_partition, "stocks": stocks_partition}


@dataclasses.dataclass(frozen=True)
class Pool:
    """``n_slices`` slices of K partitions, merged per slice into keyed
    batches.  ``parts[s][p]`` is partition ``p``'s share of slice ``s``;
    ``batches[s]`` is the keyed batch ``(type_id, ts, attr, keys)`` the
    session is fed, sorted by time."""

    slice_s: float
    parts: list
    batches: list

    @property
    def n_slices(self) -> int:
        return len(self.batches)

    def events(self, s: int) -> int:
        return len(self.batches[s][0])


def make_pool(seed: int, mix: dict, config: dict, n_slices: int) -> Pool:
    k = int(config["partitions"])
    gen = REGIMES[mix["regime"]]
    per_part = [gen(partition_rng(seed, p), mix, config["n_types"],
                    config["n_attrs"], n_slices, part=p, parts=k)
                for p in range(k)]
    key_rng = partition_rng(seed, k)
    batches = []
    for s in range(n_slices):
        parts = [per_part[p][s] for p in range(k)]
        part_of = np.concatenate([np.full(len(x.ts), p, np.int64)
                                  for p, x in enumerate(parts)])
        keys = part_of + k * key_rng.integers(0, _KEY_SALT, len(part_of))
        ts = np.concatenate([x.ts for x in parts])
        order = np.argsort(ts, kind="stable")
        batches.append((
            np.concatenate([x.type_id for x in parts])[order],
            ts[order],
            np.concatenate([x.attr for x in parts])[order],
            keys[order]))
    return Pool(float(mix["slice_s"]),
                [[per_part[p][s] for p in range(k)] for s in range(n_slices)],
                batches)


def shifted(pool: Pool, s: int):
    """Slice ``s`` of the endless replay: pool slice ``s % n`` moved
    forward by whole pool periods.  Returns ``(batch, t0, t1)``."""
    n = pool.n_slices
    shift = (s // n) * n * pool.slice_s
    tid, ts, attr, keys = pool.batches[s % n]
    t0 = s * pool.slice_s
    return (tid, (ts + np.float32(shift)).astype(np.float32), attr, keys), \
        t0, t0 + pool.slice_s


def partition_slice(pool: Pool, s: int, p: int) -> Slice:
    """Partition ``p``'s events of endless-replay slice ``s``."""
    n = pool.n_slices
    x = pool.parts[s % n][p]
    shift = np.float32((s // n) * n * pool.slice_s)
    return Slice(x.type_id, (x.ts + shift).astype(np.float32), x.attr)
