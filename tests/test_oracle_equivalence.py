"""Differential oracle harness: every execution path of the MSO engine
agrees bit-for-bit with the scalar reference.

The engine has grown five ways to run Algorithm 1 — the scalar per-point
hierarchy (``mso_search``), the single-spec batched lattice replay
(``backend="batched"``), the multi-spec vmapped pass (``mso_search_many``),
the device-sharded pass (``mso_search_many_sharded``, jit-NamedSharding and
pmap modes), and the multi-host ``('host', 'spec')`` strategy
(:mod:`repro.core.multihost`, single-host fallback).  PRs 1-2 proved their equivalences ad hoc; this is the
systematic replacement: one parametrized harness asserting, for every
alternate path, against the scalar oracle,

  * Alg.-1 selection order — the explored design sequence is identical;
  * frontier membership — same designs, in the same order;
  * PPA values — every scalar field of every frontier point is bit-exact.

across the §I scenario set and hypothesis-generated random specs, on however
many devices the process sees (CI re-runs this file under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``; the in-file
subprocess drills cover the 8-device cases regardless).

All four paths now route through the shared execution engine
(:mod:`repro.core.engine`); this file additionally pins that routing (no
pack/pad/place copies left in the path modules) and the device-sharded
Pareto extraction (``pareto.nondominated_mask_sharded`` bit-identical to the
host mask on >= 100k points, both placement modes).
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import calibrated_tech_for_reference, mso_search
from repro.core.macro import MacroSpec
from repro.core.multispec import mso_search_many, scenario_specs
from repro.core.pareto import (LOCAL_BLOCK, PARETO_EPS, dominates,
                               nondominated_mask, nondominated_mask_sharded)
from repro.core.shardspec import (mso_search_many_sharded, resolve_mode,
                                  spec_variants)

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def tech():
    return calibrated_tech_for_reference()


# ---------------------------------------------------------------------------
# The differential contract
# ---------------------------------------------------------------------------


def assert_ppa_equal(a, b):
    """Bit-exact equality of every scalar field of two MacroPPAs."""
    assert a.design.name() == b.design.name()
    assert a.paths == b.paths
    assert a.fmax_hz == b.fmax_hz
    assert a.area_um2 == b.area_um2
    assert a.area_breakdown == b.area_breakdown
    assert a.e_cycle_fj == b.e_cycle_fj
    assert a.latency_cycles == b.latency_cycles
    assert a.tops_1b == b.tops_1b
    assert a.tops_per_w_1b == b.tops_per_w_1b
    assert a.tops_per_mm2_1b == b.tops_per_mm2_1b
    assert a.meets_timing == b.meets_timing


def assert_search_identical(got, oracle):
    """The full differential contract for one spec's SearchResult."""
    assert got.spec == oracle.spec
    assert got.n_evaluated == oracle.n_evaluated
    # Alg.-1 selection order: the explored sequence, not just its set.
    assert [p.design.name() for p in got.explored] == \
           [p.design.name() for p in oracle.explored]
    # Frontier membership + bit-exact PPA per member.
    assert len(got.frontier) == len(oracle.frontier)
    for x, y in zip(got.frontier, oracle.frontier):
        assert_ppa_equal(x, y)


# Every alternate execution path, as (name, many-specs runner).  The scalar
# oracle is run per spec by the assertions below.
PATHS = {
    "batched": lambda specs, tech, res: [
        mso_search(s, None, tech, resolution=res, backend="batched")
        for s in specs],
    "multispec": lambda specs, tech, res:
        mso_search_many(specs, None, tech, resolution=res),
    "sharded-jit": lambda specs, tech, res:
        mso_search_many_sharded(specs, None, tech, resolution=res,
                                mode="jit"),
    "sharded-pmap": lambda specs, tech, res:
        mso_search_many_sharded(specs, None, tech, resolution=res,
                                mode="pmap"),
    # the ('host', 'spec') multi-host strategy (repro.core.multihost);
    # resolve falls back to the single-host pick where it is unavailable,
    # so this path is exercised (and must agree) on every runtime.
    "sharded-multihost": lambda specs, tech, res:
        mso_search_many_sharded(specs, None, tech, resolution=res,
                                mode="multihost"),
}


def _oracle(specs, tech, res):
    return [mso_search(s, None, tech, resolution=res) for s in specs]


# ---------------------------------------------------------------------------
# Scenario specs (+ ragged variant tail) vs the scalar oracle
# ---------------------------------------------------------------------------


class TestScenarioEquivalence:
    @pytest.fixture(scope="class")
    def scenario_set(self):
        # 4 scenarios + 3 posture variants = 7 specs: ragged on any even
        # device count, so the sharded paths exercise padding/masking here.
        return list(scenario_specs().values()) + spec_variants(3, seed=7)

    @pytest.fixture(scope="class")
    def oracle(self, scenario_set, tech):
        return _oracle(scenario_set, tech, 4)

    @pytest.mark.parametrize("path", sorted(PATHS))
    def test_path_matches_scalar_oracle(self, path, scenario_set, tech,
                                        oracle):
        results = PATHS[path](scenario_set, tech, 4)
        assert len(results) == len(oracle)
        for got, ref in zip(results, oracle):
            assert_search_identical(got, ref)


# ---------------------------------------------------------------------------
# Hypothesis-generated specs vs the scalar oracle
# ---------------------------------------------------------------------------


class TestRandomSpecEquivalence:
    @settings(max_examples=6, deadline=None)
    @given(h=st.sampled_from([8, 16, 32, 64]),
           w=st.sampled_from([16, 32, 64]),
           mcr=st.sampled_from([1, 2, 4]),
           ints=st.sampled_from([(2, 4), (4, 8)]),
           fps=st.sampled_from([("FP4", "FP8"), ("FP8",)]),
           f_mac=st.sampled_from([250e6, 500e6, 800e6, 1.1e9]),
           vdd=st.sampled_from([0.7, 0.9, 1.2]),
           slow_update=st.booleans())
    def test_all_paths_match_scalar_oracle(self, h, w, mcr, ints, fps, f_mac,
                                           vdd, slow_update):
        tech = calibrated_tech_for_reference()
        spec = MacroSpec(h=h, w=w, mcr=mcr, int_precisions=ints,
                         fp_precisions=fps, f_mac_hz=f_mac,
                         f_wupdate_hz=f_mac / 8 if slow_update else f_mac,
                         vdd=vdd)
        (ref,) = _oracle([spec], tech, 3)
        for path, runner in sorted(PATHS.items()):
            (got,) = runner([spec], tech, 3)
            assert_search_identical(got, ref)


# ---------------------------------------------------------------------------
# Sharded-path mechanics: mode resolution + ragged padding on 8 fake devices
# ---------------------------------------------------------------------------


class TestShardedMechanics:
    def test_mode_resolution(self):
        assert resolve_mode("auto") in ("jit", "pmap")
        assert resolve_mode("jit") == "jit"
        assert resolve_mode("pmap") == "pmap"
        with pytest.raises(ValueError):
            resolve_mode("tpu-pod")

    def test_spec_variants_deterministic_and_grouped(self, tech):
        a = spec_variants(12, seed=3)
        b = spec_variants(12, seed=3)
        assert a == b
        assert len({(s.h, s.w, s.int_precisions, s.fp_precisions)
                    for s in a}) == 1      # one geometry -> one vmap group
        assert len(set(a)) == len(a)       # duplicate-free request

    def test_ragged_counts_match_unsharded(self, tech):
        """Spec counts that do not divide the device count still return
        bit-identical per-spec results (padding is computed and discarded)."""
        for n in (1, 3, 5):
            specs = spec_variants(n, seed=n)
            ref = mso_search_many(specs, None, tech, resolution=3)
            for mode in ("jit", "pmap"):
                got = mso_search_many_sharded(specs, None, tech,
                                              resolution=3, mode=mode)
                for g, r in zip(got, ref):
                    assert_search_identical(g, r)

    def test_eight_fake_devices_bit_identical(self):
        """Subprocess drill (device count is fixed at first jax init): the
        sharded paths on 8 fake host devices, with a ragged 13-spec request,
        stay bit-identical to the unsharded multispec pass."""
        env = {**os.environ,
               "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
               "PYTHONPATH": str(REPO / "src"),
               "JAX_PLATFORMS": "cpu"}
        code = textwrap.dedent("""
            import json
            import jax
            from repro.core import calibrated_tech_for_reference
            from repro.core.multispec import mso_search_many
            from repro.core.shardspec import (mso_search_many_sharded,
                                              spec_variants)

            tech = calibrated_tech_for_reference()
            specs = spec_variants(13, seed=5)       # ragged on 8 devices
            ref = mso_search_many(specs, None, tech, resolution=3)
            verdict = {"devices": len(jax.devices())}
            for mode in ("jit", "pmap"):
                got = mso_search_many_sharded(specs, None, tech,
                                              resolution=3, mode=mode)
                verdict[mode] = all(
                    [p.design.name() for p in g.explored]
                    == [p.design.name() for p in r.explored]
                    and len(g.frontier) == len(r.frontier)
                    and all(x.paths == y.paths
                            and x.fmax_hz == y.fmax_hz
                            and x.area_um2 == y.area_um2
                            and x.area_breakdown == y.area_breakdown
                            and x.e_cycle_fj == y.e_cycle_fj
                            and x.tops_per_w_1b == y.tops_per_w_1b
                            and x.latency_cycles == y.latency_cycles
                            for x, y in zip(g.frontier, r.frontier))
                    for g, r in zip(got, ref))
            print(json.dumps(verdict))
        """)
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, env=env,
                           timeout=600, cwd=REPO)
        assert r.returncode == 0, f"scenario failed:\n{r.stderr[-3000:]}"
        last = [ln for ln in r.stdout.strip().splitlines()
                if ln.startswith("{")][-1]
        out = json.loads(last)
        assert out["devices"] == 8
        assert out["jit"] and out["pmap"]


# ---------------------------------------------------------------------------
# Engine routing: every execution path is a strategy over repro.core.engine
# ---------------------------------------------------------------------------


class TestEngineRouting:
    def test_strategies_registered_and_probed(self):
        from repro.core import engine
        assert {"jit", "vmap", "sharded-jit", "pmap",
                "multihost"} <= set(engine.STRATEGIES)
        for s in engine.STRATEGIES.values():
            assert callable(s.available) and callable(s.run)
        # the capability-probed dispatcher is the single mode authority
        assert engine.resolve_sharded_mode("auto") in ("jit", "pmap")
        with pytest.raises(ValueError):
            engine.place("warp-drive")

    def test_path_modules_are_thin_strategies(self):
        """The refactor's contract: multispec/shardspec carry no pack/pad/
        place copies of their own — shared pipeline code lives in the engine
        and the path modules only alias it."""
        from repro.core import engine, multispec, shardspec
        assert multispec._group_key is engine.group_key
        assert multispec._eval_kernel_many is engine._eval_kernel_many
        assert shardspec.resolve_mode is engine.resolve_sharded_mode
        for stale in ("_pack_group", "_unpack_group", "_grouped",
                      "_evaluate_group"):
            assert not hasattr(multispec, stale), f"copy left: {stale}"
        for stale in ("_pad_lanes", "_evaluate_group_sharded",
                      "_supports_named_sharding", "_eval_kernel_pmap"):
            assert not hasattr(shardspec, stale), f"copy left: {stale}"

    def test_plan_groups_and_execute_orders(self, tech):
        """plan() buckets same-signature specs into one group and execute()
        returns results in input order across groups."""
        from repro.core import engine
        from repro.core import subcircuits as sc
        specs = spec_variants(3, seed=2)
        mixed = [specs[0],
                 MacroSpec(h=32, w=32, mcr=2, int_precisions=(4, 8),
                           fp_precisions=("FP8",), f_mac_hz=500e6,
                           f_wupdate_hz=500e6, vdd=0.9),
                 specs[1]]
        p = engine.plan(mixed, tech, (sc.MemCellKind.SRAM_6T,), mode="vmap")
        assert sorted(len(g) for g in p.groups) == [1, 2]
        out = engine.execute(p)
        assert [lat.spec for lat, _, _ in out] == mixed


# ---------------------------------------------------------------------------
# Sharded Pareto extraction == host extraction, bit for bit
# ---------------------------------------------------------------------------


def _extraction_points(seed: int, n: int, k: int = 3) -> np.ndarray:
    """Random objectives salted with exact duplicates, eps-near ties and a
    strictly dominated row — the cases where a wrong eps band or a survivor-
    only refinement would diverge from the host mask."""
    rng = np.random.default_rng(seed)
    objs = rng.uniform(0.1, 10.0, size=(n, k))
    if n >= 8:
        objs[n // 2] = objs[0]                    # exact duplicate
        objs[n // 3] = objs[1] + PARETO_EPS / 4   # inside the tie band
        objs[n // 4] = objs[2] + 1.0              # strictly dominated
    return objs


class TestShardedParetoExtraction:
    """``nondominated_mask_sharded`` must return bit-identical frontier
    membership and output order vs the host ``nondominated_mask`` — on 1
    device in a bare tier-1 run, on 8 fake host devices in the CI re-run of
    this file, and on a pinned 8-device subprocess drill regardless."""

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=10**6),
           n=st.sampled_from([1, 7, 64, 257, 2048]),
           k=st.sampled_from([1, 2, 3, 4]),
           mode=st.sampled_from(["jit", "pmap"]))
    def test_mask_bit_identical(self, seed, n, k, mode):
        objs = _extraction_points(seed, n, k)
        host = nondominated_mask(objs)
        shard = nondominated_mask_sharded(objs, mode=mode)
        assert np.array_equal(host, shard)

    @pytest.mark.parametrize("seed", [0, 1])
    def test_host_mask_matches_all_pairs(self, seed):
        """The host mask's block-local, survivor and full-refinement phases
        give the naive all-pairs verdicts over several LOCAL_BLOCK blocks."""
        objs = _extraction_points(seed, 3 * LOCAL_BLOCK + 5, 3)
        naive = [not any(dominates(o, p) for o in objs) for p in objs]
        assert nondominated_mask(objs).tolist() == naive

    @settings(max_examples=4, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=10**6),
           chunk=st.sampled_from([64, 100, 1024]))
    def test_mask_chunk_invariant(self, seed, chunk):
        """Block boundaries must not move the verdicts."""
        objs = _extraction_points(seed, 1500, 3)
        host = nondominated_mask(objs)
        for mode in ("jit", "pmap"):
            got = nondominated_mask_sharded(objs, chunk=chunk, mode=mode)
            assert np.array_equal(host, got)

    def test_row_counts_in_one_bucket_share_compiled_passes(self):
        """Row counts within one power-of-two bucket reuse the compiled
        passes: a sweep's slices each bring their own count, and a compile
        per call costs seconds on a TPU."""
        from repro.core.pareto import _sharded_fns
        fns = _sharded_fns()
        nondominated_mask_sharded(_extraction_points(1, 2100, 3), mode="jit")
        sizes = (fns["local"]._cache_size(), fns["refine"]._cache_size())
        for n in (2200, 3000, 4096):
            objs = _extraction_points(n, n, 3)
            assert np.array_equal(nondominated_mask_sharded(objs, mode="jit"),
                                  nondominated_mask(objs))
        assert (fns["local"]._cache_size(),
                fns["refine"]._cache_size()) == sizes

    def test_frontier_order_identical_through_shared_tail(self, tech):
        """The engine's frontier tail orders identically through the host and
        the sharded mask (same survivor indices, same objective-tuple
        sort)."""
        from repro.core.engine import extract_frontier
        objs = _extraction_points(11, 4096, 3)
        assert extract_frontier(objs, nondominated_mask) == \
            extract_frontier(objs, nondominated_mask_sharded)

    def test_lattice_scale_100k_bit_identical(self):
        """The satellite contract: >= 100k random points, host vs sharded,
        identical membership and order (flatnonzero sequences equal)."""
        objs = _extraction_points(0, 100_000, 3)
        host = nondominated_mask(objs)
        for mode in ("jit", "pmap"):
            shard = nondominated_mask_sharded(objs, mode=mode)
            assert np.array_equal(host, shard), f"mask diverged in {mode}"
            assert np.array_equal(np.flatnonzero(host),
                                  np.flatnonzero(shard))

    def test_sharded_sweep_frontier_matches_unsharded(self, tech):
        """design_space_sweep_many_sharded extracts its frontiers through the
        sharded mask — indices must match the unsharded sweeps exactly."""
        from repro.core.multispec import design_space_sweep_many
        from repro.core.shardspec import design_space_sweep_many_sharded
        specs = spec_variants(3, seed=9)
        ref = design_space_sweep_many(specs, tech)
        for mode in ("jit", "pmap"):
            got = design_space_sweep_many_sharded(specs, tech, mode=mode)
            for g, r in zip(got, ref):
                assert g.extract_mask is not None
                assert g.frontier_indices() == r.frontier_indices()

    def test_extraction_eight_fake_devices_bit_identical(self):
        """Subprocess drill (device count is fixed at first jax init): 100k
        points on 8 fake host devices, both modes, bit-identical to the host
        mask computed in the same process."""
        env = {**os.environ,
               "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
               "PYTHONPATH": str(REPO / "src"),
               "JAX_PLATFORMS": "cpu"}
        code = textwrap.dedent("""
            import json
            import numpy as np
            import jax
            from repro.core.pareto import (PARETO_EPS, nondominated_mask,
                                           nondominated_mask_sharded)

            rng = np.random.default_rng(0)
            objs = rng.uniform(0.1, 10.0, size=(100_000, 3))
            objs[50_000] = objs[0]
            objs[33_333] = objs[1] + PARETO_EPS / 4
            host = nondominated_mask(objs)
            verdict = {"devices": len(jax.devices())}
            for mode in ("jit", "pmap"):
                shard = nondominated_mask_sharded(objs, mode=mode)
                verdict[mode] = bool(
                    np.array_equal(host, shard)
                    and np.array_equal(np.flatnonzero(host),
                                       np.flatnonzero(shard)))
            print(json.dumps(verdict))
        """)
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, env=env,
                           timeout=600, cwd=REPO)
        assert r.returncode == 0, f"drill failed:\n{r.stderr[-3000:]}"
        last = [ln for ln in r.stdout.strip().splitlines()
                if ln.startswith("{")][-1]
        out = json.loads(last)
        assert out["devices"] == 8
        assert out["jit"] and out["pmap"]


# ---------------------------------------------------------------------------
# Registry-backed lattice: seed + each optional axis, every path, 1/8 devices
# ---------------------------------------------------------------------------


def _axis_configs():
    from repro.core import subcircuits as sc
    from repro.core.axes import LatticeConfig
    one = (sc.MemCellKind.SRAM_6T,)
    return {
        "seed": LatticeConfig(memcells=one),
        "precision": LatticeConfig(memcells=one, precision_modes=3),
        "approx_cell": LatticeConfig(memcells=one,
                                     approx_cells=sc.APPROX_CELLS),
        "precision+approx": LatticeConfig(memcells=one, precision_modes=2,
                                          approx_cells=sc.APPROX_CELLS[:3]),
    }


class TestRegistryLatticeEquivalence:
    """The tentpole's differential contract: the registry-composed lattice —
    the seed axes AND each new optional axis (precision modes, approximate
    adder-tree cells) — evaluates bit-identically through every execution
    path, and every batched point agrees with the scalar per-design
    roll-up."""

    @pytest.fixture(scope="class")
    def spec(self):
        return MacroSpec()

    @pytest.mark.parametrize("name", sorted(_axis_configs()))
    def test_batched_points_match_scalar_rollup(self, name, spec, tech):
        """The scalar-oracle gate for each registered axis: a sample of
        lattice points (always including nonzero new-axis coordinates)
        materializes to the same PPA the scalar hierarchy computes."""
        from repro.core.batched import design_space_sweep
        from repro.core.macro import rollup
        cfg = _axis_configs()[name]
        sweep = design_space_sweep(spec, tech, config=cfg)
        lat = sweep.lattice
        rng = np.random.default_rng(len(lat))
        picks = {0, len(lat) - 1} | set(
            int(i) for i in rng.integers(0, len(lat), size=24))
        for i in sorted(picks):
            got = sweep.materialize(i)
            ref = rollup(lat.design_at(i), tech)
            assert_ppa_equal(got, ref)

    @pytest.mark.parametrize("name", sorted(_axis_configs()))
    def test_multispec_and_sharded_match_batched(self, name, spec, tech):
        """Objectives and frontier membership identical across the single-
        spec batched sweep, the vmapped multi-spec pass and both sharded
        placements on however many devices tier-1 sees."""
        from repro.core.batched import design_space_sweep
        from repro.core.multispec import design_space_sweep_many
        from repro.core.shardspec import design_space_sweep_many_sharded
        cfg = _axis_configs()[name]
        ref = design_space_sweep(spec, tech, config=cfg)
        runs = {"multispec": design_space_sweep_many(
                    [spec], tech, memcells=cfg.memcells, config=cfg)[0]}
        for mode in ("jit", "pmap"):
            runs[f"sharded-{mode}"] = design_space_sweep_many_sharded(
                [spec], tech, memcells=cfg.memcells, mode=mode,
                config=cfg)[0]
        ref_obj = ref.objectives()
        for path, sweep in runs.items():
            assert sweep.lattice.dims == ref.lattice.dims, path
            assert np.array_equal(ref_obj, sweep.objectives()), path
            assert sweep.frontier_indices() == ref.frontier_indices(), path

    def test_extended_lattice_embeds_seed_block(self, spec, tech):
        """New axes append AFTER the seed axes with the seed design at
        coordinate 0 — so the seed sweep is a strided sub-block of the
        extended sweep, bit for bit."""
        import dataclasses
        from repro.core.batched import design_space_sweep
        cfgs = _axis_configs()
        seed_sweep = design_space_sweep(spec, tech, config=cfgs["seed"])
        ext_sweep = design_space_sweep(spec, tech, config=cfgs["precision"])
        scale = ext_sweep.lattice.axis("precision").size
        assert len(ext_sweep.lattice) == len(seed_sweep.lattice) * scale
        assert np.array_equal(seed_sweep.objectives(),
                              ext_sweep.objectives()[::scale])
        for i in (0, 7, 31):
            assert dataclasses.asdict(seed_sweep.lattice.design_at(i)) == \
                dataclasses.asdict(ext_sweep.lattice.design_at(i * scale))

    def test_registry_lattice_eight_fake_devices(self, tech):
        """Subprocess drill: the extended (precision + approx-cell) lattice
        on 8 fake host devices — both sharded placements bit-identical to
        the vmapped pass."""
        env = {**os.environ,
               "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
               "PYTHONPATH": str(REPO / "src"),
               "JAX_PLATFORMS": "cpu"}
        code = textwrap.dedent("""
            import json
            import numpy as np
            import jax
            from repro.core import calibrated_tech_for_reference
            from repro.core import subcircuits as sc
            from repro.core.axes import LatticeConfig
            from repro.core.macro import MacroSpec
            from repro.core.multispec import design_space_sweep_many
            from repro.core.shardspec import (design_space_sweep_many_sharded,
                                              spec_variants)

            tech = calibrated_tech_for_reference()
            cfg = LatticeConfig(memcells=(sc.MemCellKind.SRAM_6T,),
                                precision_modes=2,
                                approx_cells=sc.APPROX_CELLS[:3])
            specs = [MacroSpec()] + spec_variants(2, seed=11)
            ref = design_space_sweep_many(specs, tech,
                                          memcells=cfg.memcells, config=cfg)
            verdict = {"devices": len(jax.devices())}
            for mode in ("jit", "pmap"):
                got = design_space_sweep_many_sharded(
                    specs, tech, memcells=cfg.memcells, mode=mode,
                    config=cfg)
                verdict[mode] = all(
                    np.array_equal(r.objectives(), g.objectives())
                    and r.frontier_indices() == g.frontier_indices()
                    for r, g in zip(ref, got))
            print(json.dumps(verdict))
        """)
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, env=env,
                           timeout=600, cwd=REPO)
        assert r.returncode == 0, f"drill failed:\n{r.stderr[-3000:]}"
        last = [ln for ln in r.stdout.strip().splitlines()
                if ln.startswith("{")][-1]
        out = json.loads(last)
        assert out["devices"] == 8
        assert out["jit"] and out["pmap"]


# ---------------------------------------------------------------------------
# Incremental re-synthesis == cold full pass, bit for bit
# ---------------------------------------------------------------------------


class TestIncrementalSweepEquivalence:
    """The service's incremental path — merging cached per-axis slice
    frontiers with a re-evaluated invalidated sublattice — must be
    indistinguishable from re-rolling the whole product."""

    def _service(self, tmp_path, config):
        from repro.service import FrontierCache, SynthesisService
        return SynthesisService(cache=FrontierCache(store_dir=tmp_path),
                                config=config)

    def test_cold_sweeps_fuse_into_one_pass(self, tmp_path, tech):
        """A burst of cold sweeps runs as one fused engine pass, and each
        lane's frontier is that spec's standalone exhaustive sweep's."""
        from repro.core import subcircuits as sc
        from repro.core.axes import LatticeConfig
        from repro.core.batched import design_space_sweep
        from repro.service import SynthesisRequest
        cfg = LatticeConfig(memcells=(sc.MemCellKind.SRAM_6T,))
        svc = self._service(tmp_path, cfg)
        specs = spec_variants(3, seed=5)
        out = svc.serve([SynthesisRequest(spec=s, tech=tech, kind="sweep")
                         for s in specs])
        assert svc.stats.fused_passes == 1 and svc.stats.misses == 3
        for s, r in zip(specs, out):
            sweep = design_space_sweep(s, tech, config=cfg)
            assert [p.design.name() for p in r.result.frontier] == \
                [sweep.lattice.design_at(i).name()
                 for i in sweep.frontier_indices()]

    def test_scoped_recalibration_merges_bit_identical(self, tmp_path, tech):
        import dataclasses
        from repro.core import subcircuits as sc
        from repro.core.axes import LatticeConfig
        from repro.service import SynthesisRequest
        cfg = LatticeConfig(memcells=(sc.MemCellKind.SRAM_6T,
                                      sc.MemCellKind.DLATCH_8T))
        svc = self._service(tmp_path / "a", cfg)
        spec = MacroSpec()
        req = SynthesisRequest(spec=spec, tech=tech, kind="sweep")
        svc.serve([req])                       # warm the slice caches
        tech2 = dataclasses.replace(tech, a_sram8t=tech.a_sram8t * 1.05)
        (warm,) = svc.serve([SynthesisRequest(spec=spec, tech=tech2,
                                              kind="sweep")])
        assert svc.stats.incremental_passes == 1
        assert svc.stats.slice_hits >= 1
        cold_svc = self._service(tmp_path / "b", cfg)
        (cold,) = cold_svc.serve([SynthesisRequest(spec=spec, tech=tech2,
                                                   kind="sweep")])
        assert cold_svc.stats.incremental_passes == 0
        assert dataclasses.asdict(warm.result) == \
            dataclasses.asdict(cold.result)

    def test_axis_growth_merges_bit_identical(self, tmp_path, tech):
        import dataclasses
        from repro.core import subcircuits as sc
        from repro.core.axes import LatticeConfig
        from repro.service import SynthesisRequest
        cfg = LatticeConfig(memcells=(sc.MemCellKind.SRAM_6T,))
        svc = self._service(tmp_path / "a", cfg)
        spec = MacroSpec()
        svc.serve([SynthesisRequest(spec=spec, tech=tech, kind="sweep")])
        grown = dataclasses.replace(cfg, rho_steps=cfg.rho_steps + (0.9,))
        (warm,) = svc.serve([SynthesisRequest(spec=spec, tech=tech,
                                              kind="sweep", config=grown)])
        assert svc.stats.incremental_passes == 1
        assert svc.stats.slice_hits == len(cfg.rho_steps)
        cold_svc = self._service(tmp_path / "b", cfg)
        (cold,) = cold_svc.serve([SynthesisRequest(spec=spec, tech=tech,
                                                   kind="sweep",
                                                   config=grown)])
        assert dataclasses.asdict(warm.result) == \
            dataclasses.asdict(cold.result)
