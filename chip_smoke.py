#!/usr/bin/env python3
"""Smoke run of the DCIM synthesis service on a TPU.

    python chip_smoke.py                # one chip: every phase below
    python chip_smoke.py --four-chips   # four chips: the sharded pass only

One process touches JAX and it starts no children.  Phases, in order:

  device    fail unless ``jax.devices()[0]`` is a TPU
  cold      a fresh SynthesisService (artifact cache in a new directory
            under ``--out``) behind a ServiceFrontend serves one burst of
            ``kind="sweep"`` requests — the four scenario specs plus 32
            posture variants, 36 lanes at the full 155,520-point lattice —
            in one fused engine pass
  oracle    per lane, 2,000 seeded lattice points and every returned
            frontier point against ``macro.rollup`` field by field; the
            scenario specs' ``kind="search"`` frontiers against
            ``searcher.mso_search``
  warm      the same burst again: every response from cache, no new pass
  select    ``select_macros`` on the full qwen3-4b GEMM inventory through
            the frontend (the ``--dcim-select`` path)
  launcher  ``repro.launch.serve.main`` in-process with ``--dcim-select``;
            the model half stays at its smoke size
  kernels   the three Pallas kernels compiled (``interpret=False``) at
            serving widths, exact against their references

``--four-chips`` runs the same burst with ``mode="sharded"`` (the
``sharded-jit`` strategy over a 4-chip ``('spec',)`` mesh, extracted with
``nondominated_mask_sharded``) against ``mode="vmap"`` on the first chip.

Phase times are printed as smoke times of one cold run, not as benchmarks.
The last line of stdout is one JSON object: ``{"ok": true, "device":
{...}}``.  Any failed check exits non-zero before that line is printed.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.metadata
import json
import sys
import tempfile
import time
from contextlib import contextmanager
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

#: Relative-difference bound the oracle check falls back to when the chip's
#: emulated float64 is not bit-identical to the host (membership must still
#: match exactly).
REL_BOUND = 1e-9

#: Seeded lattice points checked against ``macro.rollup`` per lane.
POINTS_PER_LANE = 2000

#: Resolution of the search-request frontiers, as in the oracle tests.
SEARCH_RESOLUTION = 4


class SmokeFailure(RuntimeError):
    """A check of the smoke run did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


@contextmanager
def phase(name: str, times: dict):
    t0 = time.perf_counter()
    print(f"== {name}", flush=True)
    yield
    times[name] = time.perf_counter() - t0
    print(f"   {name}: smoke time {times[name]:.1f} s (one cold run, "
          "not a benchmark)", flush=True)


# ---------------------------------------------------------------------------
# The reference comparison
# ---------------------------------------------------------------------------


def flat_ppa(p) -> dict:
    """Every compared field of a MacroPPA as ``name -> value``."""
    out = {"design": p.design.name(), "fmax_hz": p.fmax_hz,
           "area_um2": p.area_um2, "latency_cycles": p.latency_cycles,
           "tops_1b": p.tops_1b, "tops_per_mm2_1b": p.tops_per_mm2_1b,
           "meets_timing": p.meets_timing}
    for f in dataclasses.fields(p.paths):
        out[f"paths.{f.name}"] = getattr(p.paths, f.name)
    for group in ("area_breakdown", "e_cycle_fj", "tops_per_w_1b"):
        for k, v in getattr(p, group).items():
            out[f"{group}.{k}"] = v
    return out


class Comparison:
    """Field-by-field comparison of served points against the reference:
    counts exact matches and keeps the largest relative difference per
    field.  A key or non-float mismatch is a failure outright."""

    def __init__(self):
        self.points = 0
        self.exact = 0
        self.max_rel: dict[str, float] = {}

    def add(self, got, ref) -> None:
        g, r = flat_ppa(got), flat_ppa(ref)
        check(g.keys() == r.keys(), f"field sets differ: {g.keys() ^ r.keys()}")
        self.points += 1
        same = True
        for k, rv in r.items():
            gv = g[k]
            if gv == rv:
                continue
            same = False
            check(isinstance(rv, float) and isinstance(gv, float),
                  f"{k}: {gv!r} != {rv!r} ({r['design']})")
            rel = abs(gv - rv) / max(abs(rv), np.finfo(float).tiny)
            self.max_rel[k] = max(self.max_rel.get(k, 0.0), rel)
        self.exact += same

    def verdict(self, label: str) -> None:
        print(f"   {label}: {self.exact}/{self.points} points bit-identical "
              "to the reference")
        if self.exact == self.points:
            return
        for k, rel in sorted(self.max_rel.items()):
            print(f"   {label}: max relative difference {k} = {rel!r}")
        field, worst = max(self.max_rel.items(), key=lambda kv: kv[1])
        check(worst <= REL_BOUND, f"{label}: {field} relative difference "
                                  f"{worst!r} above the bound {REL_BOUND}")


def compare_search(got, ref, cmp: Comparison) -> None:
    """A served SearchResult against the scalar searcher: explored order
    and frontier membership exactly, PPA per frontier member."""
    check(got.n_evaluated == ref.n_evaluated, "n_evaluated differs")
    check([p.design.name() for p in got.explored]
          == [p.design.name() for p in ref.explored],
          "explored (Alg. 1 selection) order differs")
    check([p.design.name() for p in got.frontier]
          == [p.design.name() for p in ref.frontier],
          "frontier membership differs")
    for x, y in zip(got.frontier, ref.frontier):
        cmp.add(x, y)


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def burst_specs(n_variants: int = 32) -> list:
    from repro.core.multispec import scenario_specs
    from repro.core.shardspec import spec_variants
    return list(scenario_specs().values()) + spec_variants(n_variants)


def full_lattice():
    from repro.core import subcircuits as sc
    from repro.core.axes import LatticeConfig
    return LatticeConfig(precision_modes=3, approx_cells=sc.APPROX_CELLS)


def serve_burst(front, specs, config, mode=None) -> list:
    """One burst of sweep requests through the frontend, all in one batch."""
    from repro.service import SynthesisRequest
    reqs = [SynthesisRequest(spec=s, kind="sweep", config=config, mode=mode)
            for s in specs]
    batches = front.stats.batches
    out = front.serve(reqs, timeout=3600)
    check(front.stats.batches == batches + 1,
          f"burst split over {front.stats.batches - batches} batches")
    return out


def span_totals(prefix: str) -> dict[str, float]:
    from repro.obs import tracer
    totals: dict[str, float] = {}
    for s in tracer.drain():
        if s.name.startswith(prefix):
            totals[s.name] = totals.get(s.name, 0.0) + s.duration_s
    return totals


def cold_pass(service, front, specs, config) -> list:
    resp = serve_burst(front, specs, config)
    st = service.stats
    print(f"   cold: {len(specs)} sweep lanes x "
          f"{config_points(specs[0], config)} lattice points, "
          f"fused_passes={st.fused_passes} misses={st.misses}")
    check(st.fused_passes == 1, f"fused_passes={st.fused_passes}, want 1")
    check(st.misses == len(specs), f"misses={st.misses}")
    check(all(r.served_from == "engine" for r in resp),
          "a cold response was not served by the engine")
    for name, total in sorted(span_totals("engine.").items()):
        print(f"   cold: span {name} total {total:.3f} s")
    return resp


def config_points(spec, config) -> int:
    from repro.core.batched import DesignLattice
    return len(DesignLattice.enumerate(spec, config=config))


def oracle_check(service, front, specs, config, resp, seed: int,
                 n_points: int = POINTS_PER_LANE) -> None:
    """Lattice points and served frontiers against ``macro.rollup``;
    search frontiers against ``searcher.mso_search``."""
    from repro.core import batched as B
    from repro.core import engine as E
    from repro.core.macro import rollup
    from repro.core.searcher import mso_search
    from repro.service import SynthesisRequest
    from repro.service.service import resolve_service_mode

    tech = service.tech
    lattices = [B.DesignLattice.enumerate(s, config=config) for s in specs]
    tables = [B.SpecTables(s, tech, config=config) for s in specs]
    placement = E.place(resolve_service_mode(service.mode, len(specs)))
    evals = E.execute(E.plan_for(lattices, tables, placement=placement))

    lattice_cmp, frontier_cmp = Comparison(), Comparison()
    for lane, ((lat, _tab, ppa), r) in enumerate(zip(evals, resp)):
        valid = np.flatnonzero(lat.valid)
        rng = np.random.default_rng(seed + lane)
        for i in rng.choice(valid, size=min(n_points, valid.size),
                            replace=False):
            lattice_cmp.add(ppa.materialize(int(i)),
                            rollup(lat.design_at(int(i)), tech))
        for p in r.result.frontier + r.result.explored:
            frontier_cmp.add(p, rollup(p.design, tech))
    lattice_cmp.verdict(f"oracle: {len(specs)} lanes x seeded lattice points")
    frontier_cmp.verdict("oracle: served sweep frontier points")

    scenarios = specs[:4]
    search = front.serve([SynthesisRequest(spec=s,
                                           resolution=SEARCH_RESOLUTION)
                          for s in scenarios], timeout=3600)
    search_cmp = Comparison()
    for s, r in zip(scenarios, search):
        compare_search(r.result, mso_search(s, None, tech,
                                            resolution=SEARCH_RESOLUTION),
                       search_cmp)
    search_cmp.verdict(f"oracle: {len(scenarios)} search frontiers vs "
                       "mso_search (membership exact)")


def warm_pass(service, front, specs, config) -> None:
    passes = service.stats.fused_passes
    resp = serve_burst(front, specs, config)
    served = {r.served_from for r in resp}
    print(f"   warm: {len(resp)} responses served_from={sorted(served)}, "
          f"new fused_passes={service.stats.fused_passes - passes}")
    check(served == {"cache"}, f"warm responses served from {served}")
    check(service.stats.fused_passes == passes, "warm pass ran the engine")


def selection(front) -> None:
    from repro.configs import get_config
    from repro.core.dse import gemm_inventory
    from repro.serve.select import select_macros
    cfg = get_config("qwen3-4b")
    inv = gemm_inventory(cfg)
    sel = select_macros({cfg.name: inv}, service=front)
    est = sel.serving_for(cfg.name)
    print(f"   select: {len(inv)} GEMMs of {cfg.name}, {len(sel.pool)} "
          f"frontier candidates -> {sel.label_for(cfg.name)} "
          f"({est.tokens_per_s:.1f} tok/s roofline, {est.bottleneck}-bound)")
    check(np.isfinite(est.tokens_per_s) and est.tokens_per_s > 0,
          "selection gave no finite serving estimate")


def launcher(out: Path) -> None:
    from repro.launch.serve import main as serve_main
    trace = out / "trace.json"
    serve_main(["--arch", "qwen3-4b", "--smoke", "--dcim-select",
                "--dcim-trace", str(trace), "--batch", "2",
                "--prompt-len", "16", "--decode-steps", "8"])
    check(trace.is_file(), "launcher wrote no trace")


def kernels(interpret: bool = False, dcim_shape=(256, 2560, 9728),
            ssm_shape=(4096, 4096), csa_shape=(1024, 512)) -> None:
    """Each Pallas kernel through its public entry point, exact against its
    reference.  The scan's inputs are exact in f32 under any association
    (a in {0, 1}, small integer b), so the comparison is exact too."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.csa_tree import csa_tree_ref, csa_tree_sum
    from repro.kernels.dcim_mac import dcim_matmul_int
    from repro.kernels.dcim_mac.ref import dcim_matmul_int_ref
    from repro.kernels.ssm_scan import ssm_scan, ssm_scan_ref
    from repro.obs import tracer

    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    m, k, n = dcim_shape
    a_q = jax.random.randint(keys[0], (m, k), -128, 128, jnp.int8)
    w_q = jax.random.randint(keys[1], (k, n), -128, 128, jnp.int8)
    t, d = ssm_shape
    a = jax.random.bernoulli(keys[2], 0.9, (t, d)).astype(jnp.float32)
    b = jax.random.randint(keys[3], (t, d), -8, 9).astype(jnp.float32)
    h0 = jax.random.randint(keys[4], (d,), -8, 9).astype(jnp.float32)
    x = jax.random.randint(keys[5], csa_shape, -2**30, 2**30, jnp.int32)

    cases = {
        "dcim_mac": (lambda: dcim_matmul_int(a_q, w_q, use_pallas=True,
                                             interpret=interpret),
                     lambda: dcim_matmul_int_ref(a_q, w_q)),
        "ssm_scan": (lambda: ssm_scan(a, b, h0, use_pallas=True,
                                      interpret=interpret),
                     lambda: ssm_scan_ref(a, b, h0)),
        "csa_tree": (lambda: csa_tree_sum(x, use_pallas=True,
                                          interpret=interpret),
                     lambda: csa_tree_ref(x)),
    }
    tracer.drain()
    for name, (run, ref) in cases.items():
        with tracer.start_trace(f"smoke.{name}"):
            got = jax.block_until_ready(run())
        want = ref()
        routes = [s.tags.get("route") for s in tracer.drain()
                  if s.name == f"kernel.{name}"]
        exact = all(bool(jnp.array_equal(g, w)) for g, w in
                    zip(jax.tree.leaves(got), jax.tree.leaves(want)))
        shape = {"dcim_mac": dcim_shape, "ssm_scan": ssm_shape,
                 "csa_tree": csa_shape}[name]
        print(f"   kernels: {name} {shape} route={routes} exact={exact}")
        check(routes and all(r in ("pipelined", "grid", "tiled", "rows")
                             for r in routes), f"{name} took route {routes}")
        check(exact, f"{name} differs from its reference")


def peak_bytes(devices) -> list[int]:
    """Each device's own peak of bytes in use, as its runtime reports it."""
    stats = [dev.memory_stats() for dev in devices]
    check(all(s is not None for s in stats), "device memory stats not reported")
    return [int(s["peak_bytes_in_use"]) for s in stats]


def four_chip_phase(specs, config, out: Path) -> None:
    """The burst on a 4-chip ('spec',) mesh against one chip.  The vmap
    strategy places nothing itself, so its pass runs on the default device,
    the first chip."""
    import jax

    from repro.service import FrontierCache, ServiceFrontend, SynthesisService

    devices = jax.devices()
    check(len(devices) == 4, f"--four-chips needs 4 devices, have "
                             f"{len(devices)}")
    results = {}
    for mode in ("sharded", "vmap"):
        with tempfile.TemporaryDirectory(dir=out) as tmp:
            service = SynthesisService(cache=FrontierCache(store_dir=tmp))
            with ServiceFrontend(service, max_batch=len(specs),
                                 window=1.0) as front:
                t0 = time.perf_counter()
                resp = serve_burst(front, specs, config, mode=mode)
                dt = time.perf_counter() - t0
            totals = span_totals("engine.")
            print(f"   {mode}: {len(specs)} lanes, fused_passes="
                  f"{service.stats.fused_passes}, smoke time {dt:.1f} s; "
                  + ", ".join(f"{k} {v:.3f} s" for k, v in sorted(
                      totals.items())))
            check(service.stats.fused_passes == 1, f"{mode}: not one pass")
        results[mode] = resp
        if mode == "sharded":
            peaks = peak_bytes(devices)
            print("   sharded: peak bytes per device "
                  + ", ".join(f"{d.id}:{p}" for d, p in zip(devices, peaks)))
            check(min(peaks) >= 0.1 * max(peaks),
                  f"placement is lopsided: {peaks}")
    cmp = Comparison()
    for got, ref in zip(results["sharded"], results["vmap"]):
        r, g = ref.result, got.result
        check(g.n_evaluated == r.n_evaluated, "n_evaluated differs")
        for gs, rs in ((g.frontier, r.frontier), (g.explored, r.explored)):
            check([p.design.name() for p in gs]
                  == [p.design.name() for p in rs], "lane membership differs")
            for x, y in zip(gs, rs):
                cmp.add(x, y)
    print(f"   four-chips: {cmp.exact}/{cmp.points} lane points identical "
          "between the 4-chip sharded pass and the 1-chip vmap pass")
    check(cmp.exact == cmp.points, "sharded lanes differ from vmap lanes")


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


def versions() -> str:
    import jax
    import jaxlib
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    return f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, " \
           f"libtpu {libtpu}"


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded pass on four chips against "
                         "one chip")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "chip_smoke"),
                    help="output directory (trace, artifact cache)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (jax.devices()[0] is "
              f"{dev.platform!r}); this smoke run needs a TPU",
              file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {dev.device_kind} x{device['count']} ({versions()})",
          flush=True)

    from repro import obs
    from repro.runtime import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    obs.configure(enabled=True)

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    specs, config = burst_specs(), full_lattice()
    times: dict[str, float] = {}
    if args.four_chips:
        with phase("four-chips", times):
            four_chip_phase(specs, config, out)
    else:
        from repro.service import (FrontierCache, ServiceFrontend,
                                   SynthesisService)
        with tempfile.TemporaryDirectory(dir=out) as cache_dir:
            service = SynthesisService(
                cache=FrontierCache(store_dir=cache_dir))
            with ServiceFrontend(service, max_batch=len(specs),
                                 window=1.0) as front:
                with phase("cold", times):
                    resp = cold_pass(service, front, specs, config)
                with phase("oracle", times):
                    oracle_check(service, front, specs, config, resp,
                                 args.seed)
                with phase("warm", times):
                    warm_pass(service, front, specs, config)
                with phase("select", times):
                    selection(front)
        with phase("launcher", times):
            launcher(out)
        with phase("kernels", times):
            kernels()
    print("smoke times (s, one cold run, not a benchmark): "
          + ", ".join(f"{k}={v:.1f}" for k, v in times.items()))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
