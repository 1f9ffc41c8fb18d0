"""The one traffic generator: macro specs and arrival schedules from a
configuration's spec space, a traffic mix's parameters and ``--seed``.

A configuration file's ``spec_space`` fixes what every request shares (the
macro geometry and precisions) and the posture values drawn per request:
MAC clock ``f_mac_mhz`` (``start``..``stop`` by ``step``), a weight-update
clock from the same set and no faster than the MAC clock, a supply ``vdd``
and an ``mcr``, and, where the space lists ``weights``, each PPA preference
weight.  Every spec drawn in one run differs from every other.

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
SPECS, ORDER, HOT, WARM = 1, 2, 3, 4


@dataclass(frozen=True)
class Arrival:
    due_s: float          # seconds after the window opens
    spec: dict
    hot: bool


class SpecSpace:
    """Draws unique spec field dicts from a configuration's ``spec_space``."""

    def __init__(self, space: dict, gen: np.random.Generator):
        self.space = space
        f = space["f_mac_mhz"]
        self.freqs = list(range(f["start"], f["stop"] + 1, f["step"]))
        self.gen = gen
        self.seen: set[tuple] = set()

    def _draw(self) -> dict:
        s, g = self.space, self.gen
        i = int(g.integers(len(self.freqs)))
        j = int(g.integers(i + 1))
        spec = {"h": s["h"], "w": s["w"], "mcr": int(g.choice(s["mcr"])),
                "int_precisions": tuple(s["int_precisions"]),
                "fp_precisions": tuple(s["fp_precisions"]),
                "f_mac_hz": self.freqs[i] * 1e6,
                "f_wupdate_hz": self.freqs[j] * 1e6,
                "vdd": float(s["vdd"][int(g.integers(len(s["vdd"])))])}
        for w in ("w_power", "w_area", "w_throughput"):
            if "weights" in s:
                spec[w] = float(s["weights"][int(g.integers(
                    len(s["weights"])))])
        return spec

    def fresh(self) -> dict:
        """A spec not drawn before in this run."""
        while True:
            spec = self._draw()
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
    """One run's requests: the pool served during set-up and the window's
    schedule (open loop) or spec stream (closed loop)."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.specs = SpecSpace(config["spec_space"], rng(seed, SPECS))
        self.warm_spec = self.specs.fresh()
        pool = int(traffic.get("pool_size", 0))
        self.pool = [self.specs.fresh() for _ in range(pool)]

    @property
    def loop(self) -> str:
        return self.traffic["loop"]

    def clients(self, chips: int) -> int:
        return int(self.traffic["clients"][str(chips)])

    def closed_stream(self, clients: int):
        """The closed loop's specs: blocks of ``clients`` specs from the
        fixed ``spec_seed`` stream, each block in an order drawn from the
        run's seed."""
        space = SpecSpace(self.config["spec_space"],
                          rng(int(self.traffic["spec_seed"]), SPECS))
        order = rng(self.seed, ORDER)
        while True:
            block = [space.fresh() for _ in range(clients)]
            yield from (block[int(i)] for i in order.permutation(clients))

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

    def warm_specs(self, n: int) -> list[dict]:
        """Specs for the engine warm-up, from a stream of their own."""
        space = SpecSpace(self.config["spec_space"], rng(self.seed, WARM))
        return [space.fresh() for _ in range(n)]
