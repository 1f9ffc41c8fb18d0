"""The one traffic generator: macro specs and arrival schedules from a
configuration's spec space, a traffic mix's parameters and ``--seed``.

A configuration file's ``spec_space`` fixes the macro family of its requests
(geometry ``h`` x ``w`` and the ``int_precisions`` and ``fp_precisions``
lists) and the posture values drawn per request: MAC clock ``f_mac_mhz``
(``start``..``stop`` by ``step``), a weight-update clock from the same set
and no faster than the MAC clock, a supply ``vdd`` and an ``mcr``, and,
where the space lists ``weights``, each PPA preference weight.  Every spec
drawn in one run differs from every other.

A mixed population lists ``families`` in place of the four family keys:
each ``{"h", "w", "int_precisions", "fp_precisions", "share"}``.  Each
fresh spec picks its family by ``share`` from a stream of its own, then
draws its posture as above; the posture keys stay shared.  A space with one
family makes no pick at all.  Every family must make a valid macro spec
(powers of two, known FP formats); a bad one is an error at load.

A traffic file sets the loop:

  ``"loop": "open"``    Poisson arrivals at ``rate_per_s``.  The inter-arrival
                        gaps are drawn once from ``arrival_seed`` (the same
                        multiset in every run) and put in an order drawn from
                        ``--seed``, so seeds change which spec comes when, not
                        how much work arrives.  With ``hot_share`` > 0,
                        exactly that share of the requests re-query a pool of
                        ``pool_size`` specs (served during set-up), picked
                        Zipf(``zipf_s``) over the pool; the rest are fresh.
  ``"loop": "closed"``  ``clients[<chips>]`` clients, each keeping one request
                        on a fresh spec outstanding.  The specs come in blocks
                        of one per client, drawn from the fixed
                        ``spec_seed``; ``--seed`` orders each block.  A pass of
                        the service takes one block, so every seed does the
                        same work per pass: a sweep's cost varies by more than
                        2x with its spec, which would otherwise make the
                        seed, not the program, the largest term of the
                        spread.

A closed loop with ``recal`` re-asks known sweeps under a recalibrated
technology: ``pool_size`` specs from ``spec_seed`` are served cold during
set-up, then every request of the window asks one pool spec again with the
tech field ``field`` multiplied by one factor (``factors``: ``start``..
``stop`` by ``step``, leaving out those listed under ``except``).  The
factors come in an order fixed by ``spec_seed``; for each, the pool in
blocks of one spec per client, each block ordered by ``--seed``.  No
(spec, factor) pair repeats in a run.  One more spec from ``spec_seed``,
outside the pool, is served cold and then under ``warm_factor`` (not among
the factors) during set-up.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one named stream of one run seed (any integer)."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % 2**64, stream]))


#: Stream ids: each use of randomness draws from its own stream, so adding a
#: draw to one never shifts another.
SPECS, ORDER, HOT, WARM, RECAL = 1, 2, 3, 4, 5

#: Offset of the stream that picks each fresh spec's family, beside the
#: stream its posture is drawn from.
FAMILY_PICK = 64

#: The keys that make a macro family.
FAMILY_KEYS = ("h", "w", "int_precisions", "fp_precisions")


def families(space: dict) -> list[dict]:
    """A spec space's macro families, each checked as a macro spec: its
    ``families`` list, or the one family its top-level keys give."""
    from reference.macro import MacroSpec
    if "families" in space:
        if any(k in space for k in FAMILY_KEYS):
            raise ValueError("a spec space lists families or one family's "
                             f"{FAMILY_KEYS}, not both")
        out = space["families"]
    else:
        out = [{**{k: space[k] for k in FAMILY_KEYS}, "share": 1.0}]
    if not out:
        raise ValueError("a spec space needs at least one family")
    for f in out:
        if set(f) != set(FAMILY_KEYS) | {"share"}:
            raise ValueError(f"a family has the keys {FAMILY_KEYS} and "
                             f"'share'; got {sorted(f)}")
        if not float(f["share"]) > 0:
            raise ValueError(f"a family's share must be positive: {f}")
        MacroSpec(h=int(f["h"]), w=int(f["w"]),
                  int_precisions=tuple(f["int_precisions"]),
                  fp_precisions=tuple(f["fp_precisions"]))
    return out


@dataclass(frozen=True)
class Arrival:
    due_s: float          # seconds after the window opens
    spec: dict
    hot: bool


class SpecSpace:
    """Draws unique spec field dicts from a configuration's ``spec_space``,
    with the generators of one named stream of one seed."""

    def __init__(self, space: dict, seed: int, stream: int):
        self.space = space
        self.families = families(space)
        f = space["f_mac_mhz"]
        self.freqs = list(range(f["start"], f["stop"] + 1, f["step"]))
        self.gen = rng(seed, stream)
        self.pick = rng(seed, FAMILY_PICK + stream)
        share = np.array([float(x["share"]) for x in self.families])
        self.share = share / share.sum()
        self.seen: set[tuple] = set()

    def _draw(self, family: int | None) -> dict:
        if family is None:
            family = (int(self.pick.choice(len(self.families), p=self.share))
                      if len(self.families) > 1 else 0)
        s, fam, g = self.space, self.families[family], self.gen
        i = int(g.integers(len(self.freqs)))
        j = int(g.integers(i + 1))
        spec = {"h": fam["h"], "w": fam["w"], "mcr": int(g.choice(s["mcr"])),
                "int_precisions": tuple(fam["int_precisions"]),
                "fp_precisions": tuple(fam["fp_precisions"]),
                "f_mac_hz": self.freqs[i] * 1e6,
                "f_wupdate_hz": self.freqs[j] * 1e6,
                "vdd": float(s["vdd"][int(g.integers(len(s["vdd"])))])}
        for w in ("w_power", "w_area", "w_throughput"):
            if "weights" in s:
                spec[w] = float(s["weights"][int(g.integers(
                    len(s["weights"])))])
        return spec

    def fresh(self, family: int | None = None) -> dict:
        """A spec not drawn before in this run, of the given family (by
        index) or of one picked by share."""
        while True:
            spec = self._draw(family)
            key = tuple(sorted(spec.items()))
            if key not in self.seen:
                self.seen.add(key)
                return spec


def poisson_gaps(rate_per_s: float, seconds: float, arrival_seed: int
                 ) -> np.ndarray:
    """Inter-arrival gaps of one window, from the fixed arrival stream."""
    g = rng(arrival_seed, 0)
    gaps, total = [], 0.0
    while True:
        gap = float(g.exponential(1.0 / rate_per_s))
        if total + gap >= seconds:
            return np.asarray(gaps)
        gaps.append(gap)
        total += gap


def zipf_ranks(n: int, pool: int, s: float, gen: np.random.Generator
               ) -> np.ndarray:
    """``n`` ranks in ``[0, pool)`` with probability proportional to
    ``(rank + 1) ** -s``."""
    p = np.arange(1, pool + 1, dtype=np.float64) ** -s
    return gen.choice(pool, size=n, p=p / p.sum())


class Traffic:
    """One run's requests: the specs served during set-up and the window's
    schedule (open loop) or request stream (closed loop)."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.specs = SpecSpace(config["spec_space"], seed, SPECS)
        self.families = self.specs.families
        #: One spec of each family, served during set-up (open loop).
        self.warm = [self.specs.fresh(k) for k in range(len(self.families))]
        pool = int(traffic.get("pool_size", 0))
        self.pool = [self.specs.fresh() for _ in range(pool)]
        self.recal = traffic.get("recal")
        if self.recal is not None and \
                self.recal["warm_factor"] in self.recal_factors():
            raise ValueError("recal.warm_factor must not be one of the "
                             "window's factors")

    @property
    def loop(self) -> str:
        return self.traffic["loop"]

    def clients(self, chips: int) -> int:
        return int(self.traffic["clients"][str(chips)])

    def stream(self, clients: int):
        """The closed loop's requests as ``(spec, recal)`` pairs: ``recal``
        is ``(field, factor)`` where the request asks under a recalibrated
        tech, else None."""
        if self.recal is None:
            return ((spec, None) for spec in self.closed_stream(clients))
        return self.recal_stream(clients)

    def closed_stream(self, clients: int):
        """The closed loop's specs: blocks of ``clients`` specs from the
        fixed ``spec_seed`` stream, each block in an order drawn from the
        run's seed."""
        space = SpecSpace(self.config["spec_space"],
                          int(self.traffic["spec_seed"]), SPECS)
        order = rng(self.seed, ORDER)
        while True:
            block = [space.fresh() for _ in range(clients)]
            yield from (block[int(i)] for i in order.permutation(clients))

    def recal_specs(self) -> tuple[list[dict], dict]:
        """The pool that the window re-asks, and the spec beside it that
        warms the incremental path: the first ``pool_size`` + 1 specs of the
        fixed ``spec_seed`` stream, the same in every run."""
        space = SpecSpace(self.config["spec_space"],
                          int(self.traffic["spec_seed"]), SPECS)
        specs = [space.fresh()
                 for _ in range(int(self.recal["pool_size"]) + 1)]
        return specs[:-1], specs[-1]

    def recal_factors(self) -> list[float]:
        """The window's factors, in the order fixed by ``spec_seed``."""
        f = self.recal["factors"]
        n = int(round((f["stop"] - f["start"]) / f["step"]))
        values = [round(f["start"] + k * f["step"], 12) for k in range(n + 1)]
        values = [v for v in values if v not in f["except"]]
        order = rng(int(self.traffic["spec_seed"]), RECAL)
        return [values[int(i)] for i in order.permutation(len(values))]

    def recal_stream(self, clients: int):
        """Each factor in turn, the pool in blocks of ``clients`` specs
        under it, each block in an order drawn from the run's seed; the
        stream ends when every (spec, factor) pair has been asked."""
        pool, _ = self.recal_specs()
        if len(pool) % clients:
            raise ValueError(f"recal.pool_size {len(pool)} is not a "
                             f"multiple of the {clients} clients")
        field = self.recal["field"]
        order = rng(self.seed, ORDER)
        for factor in self.recal_factors():
            for b in range(0, len(pool), clients):
                block = pool[b:b + clients]
                yield from ((block[int(i)], (field, factor))
                            for i in order.permutation(clients))

    def schedule(self, seconds: float) -> list[Arrival]:
        """The open loop's arrivals in the window, in due order."""
        t = self.traffic
        gaps = poisson_gaps(float(t["rate_per_s"]), seconds,
                            int(t["arrival_seed"]))
        gaps = rng(self.seed, ORDER).permutation(gaps)
        due = np.cumsum(gaps)
        n = len(due)
        hot = np.zeros(n, dtype=bool)
        hot[:int(round(float(t.get("hot_share", 0.0)) * n))] = True
        hot = rng(self.seed, HOT).permutation(hot) if self.pool else hot
        ranks = (zipf_ranks(n, len(self.pool), float(t["zipf_s"]),
                            rng(self.seed, HOT + 16)) if self.pool
                 else np.zeros(n, dtype=int))
        return [Arrival(float(d), self.pool[int(r)] if h
                        else self.specs.fresh(), bool(h))
                for d, h, r in zip(due, hot, ranks)]

    def warm_specs(self, n: int, family: int = 0) -> list[dict]:
        """Specs of one family for the engine warm-up, from a stream of
        their own."""
        space = SpecSpace(self.config["spec_space"], self.seed, WARM)
        return [space.fresh(family) for _ in range(n)]
