"""Differential tests for the online synthesis service (`repro.service`).

The service's whole contract is "cheaper dispatch, same bits": every answer
it serves — in-memory cache hit, disk-artifact hit, coalesced duplicate, or
fused-miss lane — must be bit-identical to a fresh unbatched engine run of
the same spec.  Same harness style as ``tests/test_oracle_equivalence.py``:
Alg.-1 selection order, frontier membership, and bit-exact PPA per frontier
point, plus the dispatch-side contracts (N singleton requests == ONE fused
engine pass; a repeat request == ZERO engine executions; a corrupted disk
artifact is rejected, never served) and the multi-host strategy's
equivalence on 1 and 8 fake devices.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.core import calibrated_tech_for_reference, engine
from repro.core.macro import MacroSpec
from repro.core.multispec import mso_search_many, scenario_specs
from repro.core.shardspec import spec_variants
from repro.serve.select import apply_profile, select_macros
from repro.service import (CacheArtifactError, FrontierCache,
                           SynthesisService, cache_key, lattice_signature,
                           result_from_payload, result_to_payload, spec_key)

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def tech():
    return calibrated_tech_for_reference()


@pytest.fixture()
def execute_counter():
    """Counter hook on ``engine.execute`` — the observable the caching and
    coalescing contracts are asserted through."""
    calls = []
    engine.add_execute_hook(calls.append)
    yield calls
    engine.remove_execute_hook(calls.append)


# The differential contract, same style as test_oracle_equivalence.


def assert_ppa_equal(a, b):
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
    assert got.spec == oracle.spec
    assert got.n_evaluated == oracle.n_evaluated
    assert [p.design.name() for p in got.explored] == \
           [p.design.name() for p in oracle.explored]
    assert len(got.frontier) == len(oracle.frontier)
    for x, y in zip(got.frontier, oracle.frontier):
        assert_ppa_equal(x, y)


# ---------------------------------------------------------------------------
# Spec canonicalization + content addresses
# ---------------------------------------------------------------------------


class TestKeys:
    def test_spec_key_deterministic_and_structural(self):
        a = MacroSpec(h=64, w=64, mcr=2, f_mac_hz=800e6)
        b = MacroSpec(h=64, w=64, mcr=2, f_mac_hz=8e8)   # same value
        assert spec_key(a) == spec_key(a)
        assert spec_key(a) == spec_key(b)

    def test_spec_key_separates_specs(self):
        specs = spec_variants(12, seed=4) + list(scenario_specs().values())
        keys = {spec_key(s) for s in specs}
        assert len(keys) == len(set(specs))

    def test_cache_key_covers_every_ingredient(self, tech):
        import dataclasses
        spec = scenario_specs()["vision"]
        mc = SynthesisService().memcells
        base = cache_key(spec, tech, mc, 4)
        assert base == cache_key(spec, tech, mc, 4)
        # a different spec, resolution, eps band or tech calibration must
        # re-address — a stale frontier can never be served for any of them
        assert cache_key(scenario_specs()["cloud"], tech, mc, 4) != base
        assert cache_key(spec, tech, mc, 5) != base
        assert cache_key(spec, tech, mc, 4, eps=1e-9) != base
        bumped = dataclasses.replace(tech, tau_ps=tech.tau_ps * 1.01)
        assert cache_key(spec, bumped, mc, 4) != base
        assert lattice_signature(tech, mc) != lattice_signature(bumped, mc)


# ---------------------------------------------------------------------------
# Cache hits are bit-identical to fresh engine runs
# ---------------------------------------------------------------------------


class TestCacheHitIdentity:
    def test_hit_bit_identical_to_fresh_run(self, tech):
        specs = spec_variants(3, seed=11)
        fresh = mso_search_many(specs, None, tech, resolution=3)
        svc = SynthesisService(tech=tech, resolution=3)
        first = svc.synthesize_many(specs)       # misses: the fused pass
        again = svc.synthesize_many(specs)       # pure cache hits
        for f, a, b in zip(fresh, first, again):
            assert_search_identical(a, f)
            assert_search_identical(b, f)

    def test_second_call_zero_engine_executions(self, tech, execute_counter):
        spec = spec_variants(1, seed=13)[0]
        svc = SynthesisService(tech=tech, resolution=3)
        svc.synthesize(spec)
        n_cold = len(execute_counter)
        assert n_cold == 1
        svc.synthesize(spec)
        assert len(execute_counter) == n_cold    # zero new executions
        assert svc.stats.cache_hits == 1

    def test_select_macros_memoized_through_cache(self, tech,
                                                  execute_counter):
        """The satellite contract: select_macros re-synthesized the scenario
        frontier on every invocation; through the service the second call
        performs zero engine executions and selects identically."""
        from repro.core.dse import gemm_inventory
        from repro.configs import smoke_config
        workloads = {"qwen3-4b": gemm_inventory(smoke_config("qwen3-4b"))}
        svc = SynthesisService(tech=tech)
        first = select_macros(workloads, tech=tech, service=svc)
        n_cold = len(execute_counter)
        assert n_cold >= 1
        second = select_macros(workloads, tech=tech, service=svc)
        assert len(execute_counter) == n_cold    # zero engine executions
        assert second.assignment == first.assignment
        assert second.pool_labels == first.pool_labels
        assert second.summary() == first.summary()


# ---------------------------------------------------------------------------
# Coalescing: N singleton requests cost one fused pass
# ---------------------------------------------------------------------------


class TestCoalescing:
    def test_n_singletons_one_fused_pass(self, tech, execute_counter):
        specs = spec_variants(5, seed=17)
        oracle = [mso_search_many([s], None, tech, resolution=3)[0]
                  for s in specs]
        n_naive = len(execute_counter)
        assert n_naive == len(specs)             # the naive cost: N passes
        svc = SynthesisService(tech=tech, resolution=3)
        got = svc.synthesize_many(specs)
        assert len(execute_counter) == n_naive + 1   # the service cost: ONE
        assert svc.stats.fused_passes == 1
        for g, r in zip(got, oracle):
            assert_search_identical(g, r)

    def test_duplicates_coalesce_onto_one_miss(self, tech, execute_counter):
        specs = spec_variants(3, seed=19)
        stream = [specs[0], specs[1], specs[0], specs[2], specs[1], specs[0]]
        svc = SynthesisService(tech=tech, resolution=3)
        got = svc.synthesize_many(stream)
        assert len(execute_counter) == 1
        assert svc.stats.misses == 3
        assert svc.stats.coalesced == 3
        # every duplicate fans out the very result object its miss produced
        assert got[2] is got[0] and got[5] is got[0] and got[4] is got[1]

    def test_mixed_geometry_batch_still_one_execute(self, tech,
                                                    execute_counter):
        """Specs with different lattice signatures land in different vmap
        groups (engine.group_key) but still one engine entry."""
        mixed = spec_variants(2, seed=23) + [
            MacroSpec(h=32, w=32, mcr=2, int_precisions=(4, 8),
                      fp_precisions=("FP8",), f_mac_hz=500e6,
                      f_wupdate_hz=500e6, vdd=0.9)]
        oracle = mso_search_many(mixed, None, tech, resolution=3)
        n0 = len(execute_counter)
        svc = SynthesisService(tech=tech, resolution=3)
        got = svc.synthesize_many(mixed)
        assert len(execute_counter) == n0 + 1
        assert len(execute_counter[-1].groups) == 2
        for g, r in zip(got, oracle):
            assert_search_identical(g, r)


# ---------------------------------------------------------------------------
# On-disk artifact store: round trip + corrupted-artifact rejection
# ---------------------------------------------------------------------------


class TestDiskStore:
    def test_round_trip_bit_identical(self, tech, tmp_path):
        specs = spec_variants(2, seed=29)
        warm = SynthesisService(tech=tech, resolution=3,
                                cache=FrontierCache(store_dir=tmp_path))
        first = warm.synthesize_many(specs)
        # a second service over the same store: disk hits only
        cold = SynthesisService(tech=tech, resolution=3,
                                cache=FrontierCache(store_dir=tmp_path))
        again = cold.synthesize_many(specs)
        assert cold.stats.misses == 0 and cold.stats.fused_passes == 0
        assert cold.cache.stats.disk_hits == len(specs)
        for a, b in zip(first, again):
            assert_search_identical(b, a)

    def test_payload_round_trip_is_lossless(self, tech):
        (res,) = mso_search_many(spec_variants(1, seed=31), None, tech,
                                 resolution=3)
        back = result_from_payload(
            json.loads(json.dumps(result_to_payload(res))))
        assert_search_identical(back, res)
        assert back.spec == res.spec

    @pytest.mark.parametrize("corruption", [
        "not json at all {",
        json.dumps({"schema": "something-else/v1", "key": "k",
                    "result": {}}),
        json.dumps({"schema": "syndcim-frontier-artifact/v1",
                    "key": "k", "result": {"spec": {}}}),
        json.dumps([1, 2, 3]),
    ])
    def test_corrupted_artifact_rejected(self, tech, tmp_path, corruption):
        spec = spec_variants(1, seed=37)[0]
        cache = FrontierCache(store_dir=tmp_path)
        svc = SynthesisService(tech=tech, resolution=3, cache=cache)
        ref = svc.synthesize(spec)
        path = cache.artifact_path(svc.request_key(spec))
        assert path.exists()
        path.write_text(corruption)
        with pytest.raises(CacheArtifactError):
            FrontierCache.load_artifact(path)
        # a fresh service over the corrupted store treats it as a miss,
        # re-synthesizes, and heals the artifact — never serves bad bytes
        svc2 = SynthesisService(tech=tech, resolution=3,
                                cache=FrontierCache(store_dir=tmp_path))
        got = svc2.synthesize(spec)
        assert svc2.cache.stats.corrupt == 1
        assert svc2.stats.fused_passes == 1
        assert_search_identical(got, ref)
        (_, healed) = FrontierCache.load_artifact(path)   # valid again
        assert_search_identical(healed, ref)

    def test_key_mismatch_is_rejected(self, tech, tmp_path):
        """An artifact stored under the wrong address must not be served."""
        specs = spec_variants(2, seed=41)
        cache = FrontierCache(store_dir=tmp_path)
        svc = SynthesisService(tech=tech, resolution=3, cache=cache)
        svc.synthesize_many(specs)
        k0, k1 = (svc.request_key(s) for s in specs)
        os.replace(cache.artifact_path(k0), cache.artifact_path(k1))
        fresh = FrontierCache(store_dir=tmp_path)
        assert fresh.get(k1) is None
        assert fresh.stats.corrupt == 1

    def test_lru_eviction_keeps_disk_copy(self, tech, tmp_path):
        specs = spec_variants(3, seed=43)
        cache = FrontierCache(capacity=1, store_dir=tmp_path)
        svc = SynthesisService(tech=tech, resolution=3, cache=cache)
        svc.synthesize_many(specs)
        assert len(cache) == 1
        assert cache.stats.evictions == 2
        # evicted entries still answer from disk, bit-identically
        ref = mso_search_many(specs[:1], None, tech, resolution=3)[0]
        got = svc.synthesize(specs[0])
        assert svc.stats.fused_passes == 1       # no re-synthesis
        assert_search_identical(got, ref)


# ---------------------------------------------------------------------------
# apply_profile: the shared read-then-update round trip
# ---------------------------------------------------------------------------


class TestApplyProfile:
    def test_round_trip_persists_applied_weights(self, tech, tmp_path):
        from repro.core.dse import gemm_inventory
        from repro.configs import smoke_config
        from repro.serve.select import load_preference_profile
        workloads = {"qwen3-4b": gemm_inventory(smoke_config("qwen3-4b"))}
        path = tmp_path / "profile.json"
        svc = SynthesisService(tech=tech)
        sel, updated = apply_profile(
            path, lambda profile: select_macros(
                workloads, tech=tech, preference=(0.2, 0.6, 0.2),
                profile=profile, service=svc))
        assert updated.workloads["qwen3-4b"] == (0.2, 0.6, 0.2)
        back = load_preference_profile(path)
        assert back.weights_for("qwen3-4b") == (0.2, 0.6, 0.2)
        # second round: the persisted profile now overrides no-preference
        sel2, _ = apply_profile(
            path, lambda profile: select_macros(
                workloads, tech=tech, profile=profile, service=svc))
        assert sel2.preferences_applied["qwen3-4b"] == (0.2, 0.6, 0.2)
        assert sel2.assignment == sel.assignment

    def test_none_path_runs_unprofiled(self, tech):
        from repro.core.dse import gemm_inventory
        from repro.configs import smoke_config
        workloads = {"qwen3-4b": gemm_inventory(smoke_config("qwen3-4b"))}
        svc = SynthesisService(tech=tech)
        sel, updated = apply_profile(
            None, lambda profile: select_macros(workloads, tech=tech,
                                                profile=profile,
                                                service=svc))
        assert updated is None
        assert sel.preferences_applied["qwen3-4b"] is None


# ---------------------------------------------------------------------------
# Multi-host strategy: registered on the engine, bit-identical on 1 + 8 dev
# ---------------------------------------------------------------------------


class TestMultiHostStrategy:
    def test_registered_with_fallback_semantics(self):
        assert "multihost" in engine.STRATEGIES
        s = engine.STRATEGIES["multihost"]
        assert s.sharded and callable(s.default_mesh)
        assert "multihost" in engine.SHARDED_MODES
        # resolution: multihost when available, the single-host pick if not
        resolved = engine.resolve_sharded_mode("multihost")
        if s.available():
            assert resolved == "multihost"
        else:
            assert resolved in ("jit", "pmap")

    def test_host_spec_mesh_shape(self):
        import jax
        from repro.parallel.sharding import host_spec_mesh
        mesh = host_spec_mesh()
        assert tuple(mesh.axis_names) == ("host", "spec")
        assert mesh.devices.size == len(jax.devices())
        assert mesh.devices.shape[0] == jax.process_count()

    def test_host_slice_unpacks(self, tech):
        """One host's lane slice of a packed group carries every
        PackedGroup field, so the shared numpy tail runs on it and gives
        that slice's lanes of the whole group."""
        import jax
        import numpy as np
        from repro.core.multihost import _slice_packed
        p = engine.plan(spec_variants(4, seed=61), tech)
        packed = engine.pack_group(p.lattices, p.tables)
        out = engine.STRATEGIES["vmap"].run(packed, engine.place("vmap"))
        whole = engine.unpack_group(packed, out)
        part = engine.unpack_group(_slice_packed(packed, 1, 3),
                                   jax.tree.map(lambda a: a[1:3], out))
        assert len(part) == 2
        for got, ref in zip(part, whole[1:3]):
            assert got.lattice is ref.lattice
            for f in ("area", "latency", "fmax", "tops_mm2"):
                assert np.array_equal(getattr(got, f), getattr(ref, f),
                                      equal_nan=True)

    def test_multihost_matches_unsharded(self, tech):
        if not engine.STRATEGIES["multihost"].available():
            pytest.skip("multihost strategy unavailable on this runtime")
        for n in (1, 3, 5):
            specs = spec_variants(n, seed=n + 50)
            ref = mso_search_many(specs, None, tech, resolution=3)
            from repro.core.shardspec import mso_search_many_sharded
            got = mso_search_many_sharded(specs, None, tech, resolution=3,
                                          mode="multihost")
            for g, r in zip(got, ref):
                assert_search_identical(g, r)

    def test_service_through_multihost_identical(self, tech):
        if not engine.STRATEGIES["multihost"].available():
            pytest.skip("multihost strategy unavailable on this runtime")
        specs = spec_variants(4, seed=59)
        ref = mso_search_many(specs, None, tech, resolution=3)
        svc = SynthesisService(tech=tech, resolution=3, mode="multihost")
        got = svc.synthesize_many(specs)
        for g, r in zip(got, ref):
            assert_search_identical(g, r)

    def test_eight_fake_devices_bit_identical(self):
        """Subprocess drill (device count is fixed at first jax init): the
        multihost strategy on 8 fake host devices, ragged 13-spec request,
        bit-identical to the unsharded multispec pass — and the service's
        fused pass through it serves the same bits."""
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
            from repro.service import SynthesisService

            tech = calibrated_tech_for_reference()
            specs = spec_variants(13, seed=5)       # ragged on 8 devices
            ref = mso_search_many(specs, None, tech, resolution=3)

            def identical(got):
                return all(
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

            got = mso_search_many_sharded(specs, None, tech, resolution=3,
                                          mode="multihost")
            svc = SynthesisService(tech=tech, resolution=3,
                                   mode="multihost")
            served = svc.synthesize_many(specs)
            print(json.dumps({"devices": len(jax.devices()),
                              "multihost": identical(got),
                              "service": identical(served),
                              "fused_passes": svc.stats.fused_passes}))
        """)
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, env=env,
                           timeout=600, cwd=REPO)
        assert r.returncode == 0, f"drill failed:\n{r.stderr[-3000:]}"
        last = [ln for ln in r.stdout.strip().splitlines()
                if ln.startswith("{")][-1]
        out = json.loads(last)
        assert out["devices"] == 8
        assert out["multihost"] and out["service"]
        assert out["fused_passes"] == 1
