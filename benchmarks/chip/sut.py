"""The system under test, as one configuration file deploys it: the
synthesis service (``repro.service``) behind its frontend, the lattice it
serves, and the warm-up of exactly the programs the cell's traffic runs.

A request may carry a technology of its own (a recalibrated tech field,
``recalibrated``), which the service keys and serves per request.  Warm-up
runs once per macro family of the spec space: a pass that mixes families is
split by the engine into one group per family, so each family's group sizes
are warmed on their own.

This is the benchmark's only door into the program; everything it takes from
there is the served path itself and its spans and counters.
"""

from __future__ import annotations

import dataclasses

import numpy as np


class Deployment:
    """One configuration file's deployment of the service."""

    def __init__(self, cfg: dict):
        from repro.core import macro
        from repro.core import subcircuits as sc
        from repro.core.axes import LatticeConfig
        from repro.service import Priority

        lat = cfg["lattice"]
        self.lattice = LatticeConfig(
            precision_modes=int(lat["precision_modes"]),
            approx_cells=sc.APPROX_CELLS if lat["approx_cells"] else ())
        self.tech = getattr(macro, cfg["tech"])()
        req = cfg["request"]
        self.kind = req["kind"]
        self.resolution = int(req["resolution"])
        self.priority = Priority[req["priority"]]
        self.mode = cfg["service_mode"]

    def spec(self, fields: dict):
        from repro.core.macro import MacroSpec
        return MacroSpec(**fields)

    def request(self, fields: dict, tech=None):
        """The typed request for one spec, under ``tech`` where given, else
        under the service's own tech."""
        from repro.service import SynthesisRequest
        return SynthesisRequest(spec=self.spec(fields), kind=self.kind,
                                tech=tech, config=self.lattice,
                                resolution=self.resolution,
                                priority=self.priority)

    def recalibrated(self, field: str, factor: float):
        """The deployment's tech with one field multiplied by ``factor``."""
        return dataclasses.replace(
            self.tech, **{field: getattr(self.tech, field) * factor})

    def service(self, store_dir):
        from repro.service import FrontierCache, SynthesisService
        return SynthesisService(tech=self.tech, resolution=self.resolution,
                                mode=self.mode, config=self.lattice,
                                cache=FrontierCache(store_dir=store_dir))

    @staticmethod
    def frontend(service, params: dict, max_batch: int | None = None):
        from repro.service import ServiceFrontend
        return ServiceFrontend(
            service, window=float(params["window_s"]),
            max_batch=int(max_batch or params["max_batch"]),
            max_depth=int(params["max_depth"]),
            adaptive_window=bool(params["adaptive_window"]))

    # -- warm-up ------------------------------------------------------------

    def warm_engine(self, spec_fields: list[dict], lane_counts,
                    mixed: bool = False) -> None:
        """One ``engine.execute`` per lane count of one family's specs,
        placed as the service places a fused pass of that many lanes: every
        roll-up program the window can run is compiled (or loaded from the
        compile cache) here.  With ``mixed`` a group of n lanes can sit in
        any pass of n lanes or more, so it is run under the placement of
        each such pass size.  The Pareto extraction of a sweep is not
        run."""
        from repro.core import batched as B
        from repro.core import engine as E
        from repro.service.service import resolve_service_mode

        specs = [self.spec(f) for f in spec_fields]
        lattices = [B.DesignLattice.enumerate(s, config=self.lattice)
                    for s in specs]
        tables = [B.SpecTables(s, self.tech, config=self.lattice)
                  for s in specs]
        for n in lane_counts:
            sizes = [m for m in lane_counts if m >= n] if mixed else [n]
            for mode in dict.fromkeys(resolve_service_mode(self.mode, m)
                                      for m in sizes):
                E.execute(E.plan_for(lattices[:n], tables[:n],
                                     placement=E.place(mode)))

    def warm_sharded_mask(self, points: int) -> list[int]:
        """On several devices a sweep's frontiers are extracted by the
        device-sharded Pareto mask, whose programs are shaped by a
        power-of-two bucket of the row count.  Compile every bucket from the
        mask's threshold up to the bucket of the whole lattice (``points``
        rows)."""
        import jax

        from repro.core import pareto
        if len(jax.devices()) < 2 or self.kind != "sweep":
            return []
        buckets = []
        n = pareto.SHARDED_EXTRACT_MIN_POINTS
        while n < 2 * points:
            buckets.append(n)
            n *= 2
        gen = np.random.default_rng(0)
        for n in buckets:
            pareto.nondominated_mask_sharded(gen.random((n, 3)))
        return buckets


def compile_counter():
    """Counts, from JAX's own monitoring events, the programs lowered and
    the backend compilations from now on: ``{"lowered": n, "compiled": n}``.
    """
    from jax import monitoring
    counts = {"lowered": 0, "compiled": 0}

    def on_duration(name, _secs, **_kw):
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            counts["lowered"] += 1
        elif name == "/jax/core/compile/backend_compile_duration":
            counts["compiled"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    return counts


def device_info(chips_used: int) -> dict:
    """The device as JAX reports it, with the peak bytes in use of the
    fullest chip (0 where the backend reports no memory statistics)."""
    import jax
    devs = jax.devices()
    peaks = []
    for d in devs[:max(1, chips_used)]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": max(peaks)}
