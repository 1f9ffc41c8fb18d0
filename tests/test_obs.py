"""Tests for :mod:`repro.obs` — tracing, metrics, exporters — and the
instrumentation threaded through the serving stack.

The contract under test has three legs:

  * **spans tell the truth**: the frontend's request-lifecycle span
    boundaries *equal* the ``SynthesisResponse`` timestamps (same clock,
    same values — not approximations), cache-tier spans exist only for
    tiers actually probed, and every request coalesced onto one fused
    engine pass cross-links the same ``engine.pass`` span;
  * **metrics stay compatible**: the components' ``telemetry()`` dicts
    keep byte-identical key sets now that their stats are
    :class:`~repro.obs.metrics.StatsView` registry views;
  * **observation is safe**: tracing off records nothing and costs a
    contextvar read; engine hooks that mutate the hook list mid-pass
    cannot skip or double-fire their peers.
"""

import json
import math
import os
import subprocess
import sys
import textwrap
import threading
import time
from argparse import Namespace
from pathlib import Path

import pytest

from repro.core import calibrated_tech_for_reference, engine
from repro.core import batched as B
from repro.core.shardspec import spec_variants
from repro.obs import (NOOP_SPAN, MetricsRegistry, Tracer,
                       chrome_trace_events, configure, metrics_snapshot,
                       tracer, write_chrome_trace, write_spans_jsonl)
from repro.obs.metrics import Counter, Gauge, Histogram, StatsView
from repro.serve.config import (ServeConfig, load_serve_config,
                                save_serve_config, serve_config_from_args)
from repro.service import (FrontierCache, ServiceFrontend, SynthesisRequest,
                           SynthesisService)
from repro.service.cache import CacheStats
from repro.service.frontend import FrontendStats
from repro.service.registry import ArtifactRegistry, RegistryStats
from repro.service.service import ServiceStats

REPO = Path(__file__).resolve().parent.parent
TECH = calibrated_tech_for_reference()


@pytest.fixture
def traced():
    """Global tracer on at full sampling for one test, restored after."""
    configure(enabled=True, sample=1.0)
    tracer.clear()
    yield tracer
    configure(enabled=False)
    tracer.clear()


def _by_name(spans, name):
    return [s for s in spans if s.name == name]


# ---------------------------------------------------------------------------
# Metrics primitives
# ---------------------------------------------------------------------------


class TestMetrics:
    def test_counter(self):
        c = Counter("x")
        assert c.value == 0
        c.inc()
        c.inc(3)
        assert c.value == 4
        c.set(10)
        assert c.value == 10

    def test_gauge(self):
        g = Gauge("depth")
        g.set(7)
        assert g.value == 7.0

    def test_histogram_quantiles(self):
        h = Histogram("lat")
        assert h.summary() == {"count": 0, "sum": 0.0}
        assert h.quantile(0.5) == 0.0
        for v in (0.001, 0.002, 0.003, 0.004, 0.100):
            h.observe(v)
        s = h.summary()
        assert s["count"] == 5
        assert s["min"] == 0.001 and s["max"] == 0.100
        assert abs(s["sum"] - 0.110) < 1e-12
        # p50 lands in the low-millisecond buckets, p99 near the max
        assert 0.001 <= s["p50"] <= 0.005
        assert s["p99"] <= s["max"]
        with pytest.raises(ValueError):
            h.quantile(1.5)

    def test_histogram_overflow_bucket(self):
        h = Histogram("big", bounds=(1.0, 2.0))
        h.observe(100.0)
        assert h.count == 1
        assert h.quantile(1.0) == 100.0

    def test_registry_get_or_create_and_type_guard(self):
        r = MetricsRegistry("t")
        c = r.counter("a")
        assert r.counter("a") is c
        with pytest.raises(TypeError):
            r.gauge("a")
        r.histogram("h").observe(0.5)
        d = r.as_dict()
        assert d["a"] == 0 and d["h"]["count"] == 1
        assert "a 0" in r.expose() and "h{count} 1" in r.expose()

    def test_metrics_snapshot_namespaces_components(self):
        reg = MetricsRegistry("obs_test_ns")
        reg.counter("obs_test_ns/hits").inc(3)
        snap = metrics_snapshot()
        line = next(ln for ln in snap.splitlines()
                    if ln.startswith("obs_test_ns[")
                    and ln.endswith("obs_test_ns/hits 3"))
        assert line


class TestStatsView:
    def test_view_reads_and_writes_through(self):
        class S(StatsView):
            _NAMESPACE = "s"
            _FIELDS = ("a", "b")

        s = S()
        assert s.a == 0 and s.b == 0
        s.a += 2                  # get-then-set through the counter
        s.b = 5
        assert s.as_dict() == {"a": 2, "b": 5}
        assert s.metrics.counter("s/a").value == 2
        with pytest.raises(AttributeError):
            s.nope

    def test_instances_do_not_share_counters(self):
        a, b = ServiceStats(), ServiceStats()
        a.requests += 3
        assert b.requests == 0


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------


class TestTracer:
    def test_disabled_is_noop(self):
        t = Tracer()
        root = t.start_trace("request")
        assert root is NOOP_SPAN and not root
        with root as r:
            r.set_tag("k", "v")          # all no-ops, no crash
        assert t.spans() == []

    def test_child_without_parent_is_noop(self):
        t = Tracer().configure(enabled=True)
        assert t.span("orphan") is NOOP_SPAN
        assert t.spans() == []

    def test_nesting_and_ids(self):
        t = Tracer().configure(enabled=True)
        with t.start_trace("root", tags={"k": 1}) as root:
            with t.span("child") as child:
                assert child.span.trace_id == root.trace_id
                assert child.span.parent_id == root.span_id
                with t.span("grand") as g:
                    assert g.span.parent_id == child.span_id
        spans = t.drain()
        assert [s.name for s in spans] == ["grand", "child", "root"]
        assert spans[2].parent_id is None and spans[2].tags == {"k": 1}
        assert t.spans() == []               # drained

    def test_explicit_timestamps(self):
        t = Tracer().configure(enabled=True)
        root = t.start_trace("r", start_s=10.0)
        root.finish(end_s=12.5)
        (s,) = t.drain()
        assert s.start_s == 10.0 and s.end_s == 12.5
        assert s.duration_s == 2.5

    def test_exception_tags_error(self):
        t = Tracer().configure(enabled=True)
        with pytest.raises(RuntimeError):
            with t.start_trace("r"):
                with t.span("boom"):
                    raise RuntimeError("x")
        spans = t.drain()
        assert _by_name(spans, "boom")[0].tags["error"] == "RuntimeError"
        assert _by_name(spans, "r")[0].tags["error"] == "RuntimeError"

    def test_sampling_validated_and_applied(self):
        t = Tracer().configure(enabled=True)
        with pytest.raises(ValueError):
            t.configure(sample=0.0)
        with pytest.raises(ValueError):
            t.configure(sample=1.5)
        t.configure(sample=1e-9)
        roots = [t.start_trace("r") for _ in range(64)]
        assert all(r is NOOP_SPAN for r in roots)   # effectively never sampled
        t.configure(sample=1.0)
        assert t.start_trace("r") is not NOOP_SPAN

    def test_activate_cross_thread(self):
        t = Tracer().configure(enabled=True)
        root = t.start_trace("root")
        seen = {}

        def worker(ctx):
            with t.activate(ctx):
                with t.span("work") as w:
                    seen["trace"] = w.span.trace_id
                    seen["parent"] = w.span.parent_id

        th = threading.Thread(target=worker, args=(root.context,))
        th.start()
        th.join()
        root.finish()
        assert seen["trace"] == root.trace_id
        assert seen["parent"] == root.span_id

    def test_bounded_buffer_drops_and_counts(self):
        t = Tracer().configure(enabled=True)
        t.MAX_SPANS = 2
        with t.start_trace("root") as root:
            for _ in range(4):
                with t.span("s"):
                    pass
        assert len(t.spans()) == 2
        assert root.span.end_s is not None   # finish still safe past the cap

    def test_finish_idempotent(self):
        t = Tracer().configure(enabled=True)
        root = t.start_trace("r")
        root.finish(end_s=1.0)
        root.finish(end_s=9.0)               # second finish is a no-op
        (s,) = t.drain()
        assert s.end_s == 1.0


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------


class TestExport:
    def _spans(self):
        t = Tracer().configure(enabled=True)
        with t.start_trace("request", start_s=1.0, tags={"kind": "search"}) as r:
            t.start("cache.mem", parent=r.context, start_s=1.1).finish(end_s=1.2)
            r.finish(end_s=2.0)
        with t.start_trace("engine.pass", start_s=1.5) as p:
            p.finish(end_s=1.9)
        return t.drain()

    def test_jsonl_round_trip(self, tmp_path):
        spans = self._spans()
        path = tmp_path / "spans.jsonl"
        assert write_spans_jsonl(spans, path) == 3
        lines = [json.loads(ln) for ln in path.read_text().splitlines()]
        assert len(lines) == 3
        assert {ln["name"] for ln in lines} == {"request", "cache.mem",
                                               "engine.pass"}
        req = next(ln for ln in lines if ln["name"] == "request")
        assert req["tags"] == {"kind": "search"}
        assert req["duration_s"] == 1.0

    def test_chrome_trace_events(self, tmp_path):
        spans = self._spans()
        events = chrome_trace_events(spans)
        xs = [e for e in events if e["ph"] == "X"]
        ms = [e for e in events if e["ph"] == "M"]
        assert len(xs) == 3 and len(ms) == 2      # one lane per trace
        cache = next(e for e in xs if e["name"] == "cache.mem")
        # microseconds relative to the earliest span (start_s=1.0)
        assert abs(cache["ts"] - 0.1e6) < 1.0
        assert abs(cache["dur"] - 0.1e6) < 1.0
        assert cache["cat"] == "cache"
        assert cache["args"]["parent_id"]
        req = next(e for e in xs if e["name"] == "request")
        assert req["args"]["kind"] == "search"
        lane_names = {m["args"]["name"] for m in ms}
        assert any(n.startswith("request[") for n in lane_names)
        assert any(n.startswith("engine.pass[") for n in lane_names)
        path = tmp_path / "trace.json"
        assert write_chrome_trace(spans, path) == 3
        doc = json.loads(path.read_text())
        assert set(doc) == {"traceEvents", "displayTimeUnit"}

    def test_empty_export(self, tmp_path):
        assert chrome_trace_events([]) == []
        assert write_chrome_trace([], tmp_path / "t.json") == 0


# ---------------------------------------------------------------------------
# Telemetry compatibility: byte-identical key sets
# ---------------------------------------------------------------------------


class TestTelemetryCompat:
    def test_service_stats_keys(self):
        assert tuple(ServiceStats().as_dict()) == (
            "requests", "cache_hits", "coalesced", "misses", "fused_passes",
            "slice_hits", "incremental_passes", "claims_acquired",
            "claim_waits", "claim_hits", "claim_timeouts")

    def test_cache_stats_keys(self):
        assert tuple(CacheStats().as_dict()) == (
            "gets", "hits", "disk_hits", "shared_hits", "misses", "puts",
            "evictions", "evictions_lost", "corrupt")

    def test_registry_stats_keys(self):
        assert tuple(RegistryStats().as_dict()) == (
            "hits", "misses", "fills", "fill_noops", "corrupt",
            "claims_acquired", "claims_lost", "claims_broken",
            "claims_released", "evictions")

    def test_frontend_stats_keys(self):
        assert tuple(FrontendStats().as_dict()) == (
            "submitted", "served", "shedded", "batches", "max_batch",
            "depth_hwm")

    def test_registry_telemetry_adds_entries(self, tmp_path):
        reg = ArtifactRegistry(tmp_path)
        t = reg.telemetry()
        assert set(t) == set(RegistryStats().as_dict()) | {"entries"}

    def test_service_telemetry_sections(self, tmp_path):
        svc = SynthesisService(tech=TECH, resolution=3)
        assert set(svc.telemetry()) == {"service", "cache"}
        svc = SynthesisService(
            tech=TECH, resolution=3,
            cache=FrontierCache(registry=ArtifactRegistry(tmp_path)))
        assert set(svc.telemetry()) == {"service", "cache", "registry"}


# ---------------------------------------------------------------------------
# Service instrumentation: tier spans, engine-pass cross-links
# ---------------------------------------------------------------------------


class TestServiceSpans:
    def _serve(self, svc, specs):
        roots = [tracer.start_trace("request") for _ in specs]
        responses = svc.serve([SynthesisRequest(spec=s) for s in specs],
                              contexts=[r.context for r in roots])
        for r in roots:
            r.finish()
        return responses

    def test_mem_only_cache_probes_one_tier(self, traced):
        svc = SynthesisService(tech=TECH, resolution=3)
        specs = spec_variants(2, seed=21)
        self._serve(svc, specs)
        spans = tracer.drain()
        assert len(_by_name(spans, "cache.mem")) == 2
        assert not _by_name(spans, "cache.disk")
        assert not _by_name(spans, "cache.registry")
        assert not _by_name(spans, "cache.claim")
        assert all(s.tags["outcome"] == "miss"
                   for s in _by_name(spans, "cache.mem"))

    def test_all_tiers_probed_when_configured(self, traced, tmp_path):
        svc = SynthesisService(
            tech=TECH, resolution=3,
            cache=FrontierCache(store_dir=tmp_path / "store",
                                registry=ArtifactRegistry(tmp_path / "reg")))
        (spec,) = spec_variants(1, seed=22)
        self._serve(svc, [spec])
        spans = tracer.drain()
        for tier in ("cache.mem", "cache.disk", "cache.registry"):
            (s,) = _by_name(spans, tier)
            assert s.tags["outcome"] == "miss"
        (claim,) = _by_name(spans, "cache.claim")
        assert claim.tags["outcome"] == "acquired"
        # warm pass: memory answers, deeper tiers never probed again
        self._serve(svc, [spec])
        spans = tracer.drain()
        (mem,) = _by_name(spans, "cache.mem")
        assert mem.tags["outcome"] == "hit"
        assert not _by_name(spans, "cache.disk")
        assert not _by_name(spans, "cache.registry")

    def test_shared_engine_pass_cross_links(self, traced):
        svc = SynthesisService(tech=TECH, resolution=3)
        specs = spec_variants(2, seed=23)
        stream = [specs[0], specs[1], specs[0]]      # one coalesced dup
        self._serve(svc, stream)
        spans = tracer.drain()
        (engine_pass,) = _by_name(spans, "engine.pass")
        assert engine_pass.tags["n_requests"] == 2   # dup coalesced away
        links = _by_name(spans, "request.engine")
        assert len(links) == 3
        assert {l.tags["engine_pass"] for l in links} == {engine_pass.span_id}
        assert {l.tags["engine_trace"] for l in links} == {engine_pass.trace_id}
        assert sorted(l.tags["coalesced"] for l in links) == [False, False,
                                                              True]
        # the links live in the REQUESTS' traces, not the pass's own
        assert all(l.trace_id != engine_pass.trace_id for l in links)
        # phase children inside the pass trace
        for phase in ("engine.plan", "engine.execute"):
            (p,) = _by_name(spans, phase)
            assert p.trace_id == engine_pass.trace_id
        assert not _by_name(spans, "engine.place")
        extracts = _by_name(spans, "engine.extract")
        assert len(extracts) == 2
        execute = _by_name(spans, "engine.execute")[0]
        assert execute.tags["n_specs"] == 2
        assert execute.tags["mode"] and execute.tags["n_dev"] >= 1

    def test_untraced_serve_records_nothing(self, traced):
        svc = SynthesisService(tech=TECH, resolution=3)
        svc.serve([SynthesisRequest(spec=spec_variants(1, seed=24)[0])])
        # no contexts passed -> no request-side spans; the fused pass still
        # records its own engine.pass trace (it is a trace root)
        spans = tracer.drain()
        assert not _by_name(spans, "cache.mem")
        assert len(_by_name(spans, "engine.pass")) == 1

    def test_tracing_off_records_nothing_at_all(self):
        assert not tracer.enabled
        svc = SynthesisService(tech=TECH, resolution=3)
        svc.serve([SynthesisRequest(spec=spec_variants(1, seed=25)[0])])
        assert tracer.spans() == []


# ---------------------------------------------------------------------------
# Frontend: span boundaries EQUAL response timestamps, scheduler thread
# ---------------------------------------------------------------------------


class TestFrontendSpans:
    def test_span_boundaries_equal_response_timestamps(self, traced):
        svc = SynthesisService(tech=TECH, resolution=3)
        specs = spec_variants(3, seed=31)
        with ServiceFrontend(svc, window=0.02, max_batch=8) as front:
            tickets = [front.submit(SynthesisRequest(spec=s)) for s in specs]
            responses = [t.result(timeout=600) for t in tickets]
        spans = tracer.drain()
        roots = _by_name(spans, "request")
        assert len(roots) == 3
        for resp in responses:
            root = next(s for s in roots if s.start_s == resp.queued_at)
            assert root.end_s == resp.served_at
            assert root.tags["served_from"] == resp.served_from
            queued = next(s for s in _by_name(spans, "request.queued")
                          if s.parent_id == root.span_id)
            batched = next(s for s in _by_name(spans, "request.batched")
                           if s.parent_id == root.span_id)
            # EXACT equality: same clock, same stamps — not "within 1ms"
            assert queued.start_s == resp.queued_at
            assert queued.end_s == resp.batched_at
            assert batched.start_s == resp.batched_at
            assert batched.end_s == resp.served_at
            assert batched.tags["batch_size"] >= 1
        # per-request latency histogram observed once per served request
        from repro.obs.metrics import get_registry
        assert get_registry().histogram(
            "frontend/request_latency_s").count >= 3

    def test_shed_finishes_span_with_reason(self, traced):
        svc = SynthesisService(tech=TECH, resolution=3)
        front = ServiceFrontend(svc, max_depth=1, start=False)
        specs = spec_variants(3, seed=32)
        front.submit(SynthesisRequest(spec=specs[0]))
        t2 = front.submit(SynthesisRequest(spec=specs[1]))   # over depth
        assert t2.done()
        shed = next(s for s in tracer.spans() if s.name == "request"
                    and "shedded" in s.tags)
        assert shed.tags["shedded"] == "queue_full"
        front.close(drain=False)


# ---------------------------------------------------------------------------
# Engine hook-list mutation hazards (regression)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def small_plan():
    (spec,) = spec_variants(1, seed=41)
    lattice = B.DesignLattice.enumerate(spec)
    tables = B.SpecTables(spec, TECH)
    return engine.plan_for([lattice], [tables])


class TestHookMutationSafety:
    def test_self_removing_execute_hook_does_not_skip_peers(self, small_plan):
        calls = []

        def hook_a(plan):
            calls.append("a")
            engine.remove_execute_hook(hook_a)

        def hook_b(plan):
            calls.append("b")

        engine.add_execute_hook(hook_a)
        engine.add_execute_hook(hook_b)
        try:
            engine.execute(small_plan)
            assert calls == ["a", "b"]       # b neither skipped nor doubled
            engine.execute(small_plan)
            assert calls == ["a", "b", "b"]  # a really removed itself
        finally:
            for h in (hook_a, hook_b):
                try:
                    engine.remove_execute_hook(h)
                except ValueError:
                    pass

    def test_self_removing_latency_hook_does_not_skip_peers(self, small_plan):
        calls = []

        def hook_a(plan, elapsed_s):
            calls.append(("a", elapsed_s > 0))
            engine.remove_latency_hook(hook_a)

        def hook_b(plan, elapsed_s):
            calls.append(("b", elapsed_s > 0))

        engine.add_latency_hook(hook_a)
        engine.add_latency_hook(hook_b)
        try:
            engine.execute(small_plan)
            assert calls == [("a", True), ("b", True)]
        finally:
            for h in (hook_a, hook_b):
                try:
                    engine.remove_latency_hook(h)
                except ValueError:
                    pass

    def test_hook_added_during_pass_fires_next_pass_only(self, small_plan):
        calls = []

        def late(plan):
            calls.append("late")

        def adder(plan):
            calls.append("adder")
            engine.add_execute_hook(late)

        engine.add_execute_hook(adder)
        try:
            engine.execute(small_plan)
            assert calls == ["adder"]        # snapshot iteration: not yet
            engine.execute(small_plan)
            assert calls == ["adder", "late", "adder"] or \
                calls == ["adder", "adder", "late"]
        finally:
            for h in (adder, late):
                try:
                    engine.remove_execute_hook(h)
                except ValueError:
                    pass


# ---------------------------------------------------------------------------
# ServeConfig: trace / trace_sample / kernel_profile posture
# ---------------------------------------------------------------------------


def _args(**kw):
    base = dict(dcim_config=None, dcim_select=False, dcim_pref=None,
                dcim_profile=None, dcim_cache=None, dcim_macros=None,
                dcim_trace=None, dcim_trace_sample=None,
                dcim_kernel_profile=None)
    base.update(kw)
    return Namespace(**base)


class TestServeConfigObs:
    def test_round_trip_with_obs_fields(self, tmp_path):
        cfg = ServeConfig(trace="trace.json", trace_sample=0.25,
                          kernel_profile="kp.json")
        path = tmp_path / "serve.json"
        save_serve_config(path, cfg)
        assert load_serve_config(path) == cfg

    def test_trace_sample_validated(self):
        with pytest.raises(ValueError):
            ServeConfig(trace_sample=0.0)
        with pytest.raises(ValueError):
            ServeConfig(trace_sample=1.0001)
        assert ServeConfig(trace_sample=1).trace_sample == 1.0

    def test_legacy_artifact_without_obs_keys_loads(self, tmp_path):
        path = tmp_path / "serve.json"
        path.write_text(json.dumps({"schema": "syndcim-serve-config/v1",
                                    "select": True, "macros": 64}))
        cfg = load_serve_config(path)
        assert cfg.trace is None and cfg.trace_sample == 1.0
        assert cfg.kernel_profile is None

    def test_cli_flags_override_file(self, tmp_path):
        path = tmp_path / "serve.json"
        save_serve_config(path, ServeConfig(trace="file.json",
                                            trace_sample=0.5))
        got = serve_config_from_args(_args(dcim_config=str(path),
                                           dcim_trace="cli.json",
                                           dcim_trace_sample=0.75,
                                           dcim_kernel_profile="kp.json"))
        assert got.trace == "cli.json" and got.trace_sample == 0.75
        assert got.kernel_profile == "kp.json"
        got = serve_config_from_args(_args(dcim_config=str(path)))
        assert got.trace == "file.json" and got.trace_sample == 0.5


# ---------------------------------------------------------------------------
# Kernel-profile artifact round trip
# ---------------------------------------------------------------------------


class TestKernelProfileArtifact:
    def _profiles(self):
        from repro.kernels.profile import KernelProfile
        from repro.kernels.tiles import TileConfig
        return [
            KernelProfile(kernel="dcim_mac", shape=(128, 128, 128),
                          tile=TileConfig(), t_copy_us=10.0,
                          t_compute_us=40.0, t_fused_us=50.0,
                          bytes_moved=1000, flops=2000,
                          compute_measured=True),
            KernelProfile(kernel="ssm_scan", shape=(512, 128),
                          tile=TileConfig(), t_copy_us=30.0,
                          t_compute_us=10.0, t_fused_us=30.0,
                          bytes_moved=500, flops=800,
                          compute_measured=True),
        ]

    def test_payload_round_trip(self, tmp_path):
        from repro.kernels.profile import (PROFILE_SCHEMA,
                                           fraction_from_profile_artifact,
                                           fraction_from_profiles,
                                           load_profile_artifact,
                                           profiles_payload)
        profiles = self._profiles()
        payload = profiles_payload(profiles)
        assert payload["schema"] == PROFILE_SCHEMA
        expect = fraction_from_profiles(profiles)
        assert math.isclose(payload["fraction"], expect)
        path = tmp_path / "kp.json"
        path.write_text(json.dumps(payload))
        data = load_profile_artifact(path)
        assert len(data["profiles"]) == 2
        assert math.isclose(fraction_from_profile_artifact(path), expect)

    def test_legacy_bare_list_upgraded(self, tmp_path):
        from repro.kernels.profile import (fraction_from_profile_artifact,
                                           fraction_from_profiles,
                                           load_profile_artifact)
        profiles = self._profiles()
        path = tmp_path / "legacy.json"
        path.write_text(json.dumps([p.as_dict() for p in profiles]))
        data = load_profile_artifact(path)
        assert data["backend"] is None
        assert math.isclose(fraction_from_profile_artifact(path),
                            fraction_from_profiles(profiles))

    def test_bad_schema_and_bad_fraction_rejected(self, tmp_path):
        from repro.kernels.profile import (fraction_from_profile_artifact,
                                           load_profile_artifact)
        path = tmp_path / "bad.json"
        path.write_text(json.dumps({"schema": "other/v1"}))
        with pytest.raises(ValueError, match="not a kernel profile"):
            load_profile_artifact(path)
        path.write_text(json.dumps({"schema": "syndcim-kernel-profile/v1",
                                    "fraction": 0.0, "profiles": []}))
        with pytest.raises(ValueError, match="fraction"):
            fraction_from_profile_artifact(path)

    def test_select_macros_threads_kernel_fraction(self):
        from repro.roofline.dcim import dcim_serving_bound
        from repro.core.dse import GemmShape
        gemms = [GemmShape("g", 128, 128, 128)]
        full = dcim_serving_bound(gemms, 1e-3)
        derated = dcim_serving_bound(gemms, 1e-3, kernel_fraction=0.5)
        assert derated.tokens_per_s < full.tokens_per_s


# ---------------------------------------------------------------------------
# Kernel dispatch instrumentation
# ---------------------------------------------------------------------------


class TestKernelDispatchSpans:
    def test_dcim_mac_interpret_dispatch(self, traced):
        import jax.numpy as jnp
        import numpy as np
        from repro.kernels import dcim_matmul_int
        from repro.obs.metrics import get_registry
        rng = np.random.default_rng(0)
        a = jnp.asarray(rng.integers(-8, 8, (8, 128)), jnp.int8)
        w = jnp.asarray(rng.integers(-8, 8, (128, 128)), jnp.int8)
        before = get_registry().counter("kernel/dcim_mac/dispatch").value
        with tracer.start_trace("request"):
            dcim_matmul_int(a, w, use_pallas=True, interpret=True)
        spans = tracer.drain()
        (k,) = _by_name(spans, "kernel.dcim_mac")
        assert k.tags["shape"] == "8x128x128"
        assert k.tags["route"] in ("pipelined", "grid")
        assert k.tags["tile_source"] == "default"
        assert isinstance(k.tags["tile"], dict)
        reg = get_registry()
        assert reg.counter("kernel/dcim_mac/dispatch").value == before + 1
        assert reg.counter(
            f"kernel/dcim_mac/route/{k.tags['route']}").value >= 1

    def test_xla_path_source_none(self, traced):
        import jax.numpy as jnp
        import numpy as np
        from repro.kernels import dcim_matmul_int
        rng = np.random.default_rng(0)
        a = jnp.asarray(rng.integers(-8, 8, (4, 16)), jnp.int8)
        w = jnp.asarray(rng.integers(-8, 8, (16, 8)), jnp.int8)
        with tracer.start_trace("request"):
            dcim_matmul_int(a, w, use_pallas=False)
        (k,) = _by_name(tracer.drain(), "kernel.dcim_mac")
        assert k.tags["route"] == "xla" and k.tags["tile_source"] == "none"
        assert "tile" not in k.tags

    def test_lookup_with_source_default(self):
        from repro.kernels.autotune import lookup_with_source
        cfg, source = lookup_with_source("dcim_mac", (64, 64, 64))
        assert source in ("memo", "registry", "default")
        assert cfg is not None


# ---------------------------------------------------------------------------
# 8-fake-device drill: spans under the real scheduler thread + sharding
# ---------------------------------------------------------------------------


class TestObsEightDevices:
    def test_eight_fake_devices_span_alignment(self):
        """Subprocess drill: tracing on, a 6-spec stream through the async
        frontend over a multihost-mode service on 8 fake devices — every
        request's span boundaries equal its response stamps, all requests
        cross-link one engine pass."""
        env = {**os.environ,
               "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
               "PYTHONPATH": str(REPO / "src"),
               "JAX_PLATFORMS": "cpu"}
        code = textwrap.dedent("""
            import json
            import jax
            from repro.core import calibrated_tech_for_reference
            from repro.core.shardspec import spec_variants
            from repro.obs import configure, tracer
            from repro.service import (ServiceFrontend, SynthesisRequest,
                                       SynthesisService)

            configure(enabled=True, sample=1.0)
            tech = calibrated_tech_for_reference()
            specs = spec_variants(6, seed=9)
            svc = SynthesisService(tech=tech, resolution=3,
                                   mode="multihost")
            with ServiceFrontend(svc, window=0.05, max_batch=8) as front:
                tickets = [front.submit(SynthesisRequest(spec=s))
                           for s in specs]
                responses = [t.result(timeout=600) for t in tickets]
            spans = tracer.drain()
            roots = [s for s in spans if s.name == "request"]
            aligned = all(
                any(s.start_s == r.queued_at and s.end_s == r.served_at
                    for s in roots)
                for r in responses)
            passes = [s for s in spans if s.name == "engine.pass"]
            links = [s for s in spans if s.name == "request.engine"]
            pass_ids = {s.span_id for s in passes}
            linked = all(l.tags["engine_pass"] in pass_ids for l in links)
            print(json.dumps({
                "devices": len(jax.devices()),
                "requests": len(roots),
                "aligned": aligned,
                "passes": len(passes),
                "links": len(links),
                "linked": linked,
                "served": front.stats.served,
                "shedded": front.stats.shedded}))
        """)
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, env=env,
                           timeout=600, cwd=REPO)
        assert r.returncode == 0, f"drill failed:\n{r.stderr[-3000:]}"
        last = [ln for ln in r.stdout.strip().splitlines()
                if ln.startswith("{")][-1]
        out = json.loads(last)
        assert out["devices"] == 8
        assert out["requests"] == 6 and out["served"] == 6
        assert out["shedded"] == 0
        assert out["aligned"]
        assert out["passes"] >= 1 and out["linked"]
        assert out["links"] == 6


# ---------------------------------------------------------------------------
# Inside the engine: execute and extract children, host spans, mirroring
# ---------------------------------------------------------------------------

_EXECUTE_DRILL = textwrap.dedent("""
    import json, sys
    from repro.core import calibrated_tech_for_reference, engine
    from repro.core.shardspec import spec_variants
    from repro.obs import configure, tracer

    mode = sys.argv[1]
    configure(enabled=True, sample=1.0)
    p = engine.plan(spec_variants(3, seed=7), calibrated_tech_for_reference(),
                    mode=mode)
    engine.execute(p)                   # compile outside the traced pass
    tracer.clear()
    with tracer.start_trace("drill"):
        engine.execute(p)
    print(json.dumps([s.as_dict() for s in tracer.drain()]))
""")


class TestEngineChildSpans:
    @pytest.mark.parametrize("mode,devices", [("vmap", 1),
                                              ("sharded-jit", 2)])
    def test_execute_span_parents_its_steps(self, mode, devices):
        env = {**os.environ, "PYTHONPATH": str(REPO / "src"),
               "JAX_PLATFORMS": "cpu",
               "XLA_FLAGS": f"--xla_force_host_platform_device_count="
                            f"{devices}"}
        r = subprocess.run([sys.executable, "-c", _EXECUTE_DRILL, mode],
                           capture_output=True, text=True, env=env,
                           timeout=600, cwd=REPO)
        assert r.returncode == 0, r.stderr[-3000:]
        spans = json.loads(r.stdout.strip().splitlines()[-1])
        (execute,) = [s for s in spans if s["name"] == "engine.execute"]
        assert execute["tags"]["n_specs"] == 3
        assert execute["tags"]["mode"] == mode
        assert execute["tags"]["n_dev"] == devices
        assert execute["tags"]["n_groups"] == 1
        children = [s for s in spans if s["parent_id"] == execute["span_id"]]
        assert [s["name"] for s in children] == [
            "execute.pack", "execute.wait", "execute.fetch", "execute.unpack"]
        for c in children:
            assert execute["start_s"] <= c["start_s"] <= c["end_s"] \
                <= execute["end_s"]
        fetch = children[2]
        assert fetch["tags"]["arrays"] > 0 and fetch["tags"]["bytes"] > 0

    def test_cold_sweep_records_masks_and_puts(self, traced, tmp_path):
        from repro.core import subcircuits as sc
        from repro.core.axes import LatticeConfig
        cfg = LatticeConfig(memcells=(sc.MemCellKind.SRAM_6T,))
        svc = SynthesisService(tech=TECH, config=cfg,
                               cache=FrontierCache(store_dir=tmp_path))
        (spec,) = spec_variants(1, seed=5)
        svc.serve([SynthesisRequest(spec=spec, tech=TECH, kind="sweep")])
        spans = tracer.drain()
        by_id = {s.span_id: s for s in spans}
        masks = _by_name(spans, "extract.mask")
        assert masks
        for m in masks:
            assert by_id[m.parent_id].name == "engine.extract"
            assert m.tags["where"] == "host" and m.tags["rows"] > 0
        puts = _by_name(spans, "store.put")
        assert len(puts) == svc.cache.stats.puts > 1
        assert {p.tags["tiers"] for p in puts} == {"mem,disk"}
        # the full-sweep result is written after its engine.extract closes
        (extract,) = _by_name(spans, "engine.extract")
        assert puts[-1].start_s >= extract.end_s
        assert by_id[puts[-1].parent_id].name == "engine.pass"
        # nothing is named under the readers' prefixes
        assert not [s for s in spans if s.name.startswith("engine.extract.")
                    or s.name.startswith("cache.put")]

    def test_host_masks_tag_distinct_and_survivors(self, traced, tmp_path):
        """Each host ``extract.mask`` span counts the rows left after the
        duplicate collapse and the rows refined, never more than it masked;
        the full-lattice masks collapse real duplicates."""
        from repro.core import subcircuits as sc
        from repro.core.axes import LatticeConfig
        from repro.core.pareto import WALK_BLOCK
        cfg = LatticeConfig(memcells=(sc.MemCellKind.SRAM_6T,))
        svc = SynthesisService(tech=TECH, config=cfg,
                               cache=FrontierCache(store_dir=tmp_path))
        (spec,) = spec_variants(1, seed=5)
        svc.serve([SynthesisRequest(spec=spec, tech=TECH, kind="sweep")])
        masks = _by_name(tracer.drain(), "extract.mask")
        assert masks
        for m in masks:
            t = m.tags
            assert 0 <= t["survivors"] <= t["distinct"] <= t["rows"]
            if t["rows"] <= WALK_BLOCK:
                assert t["survivors"] == t["distinct"] == t["rows"]
        assert any(m.tags["distinct"] < m.tags["rows"] for m in masks)


class TestFrontendIdleAndWindow:
    def test_request_window_lies_inside_request_queued(self, traced):
        svc = SynthesisService(tech=TECH, resolution=3)
        specs = spec_variants(3, seed=33)
        with ServiceFrontend(svc, window=0.02, max_batch=8) as front:
            time.sleep(0.2)              # the scheduler waits for arrivals
            tickets = [front.submit(SynthesisRequest(spec=s)) for s in specs]
            for t in tickets:
                t.result(timeout=600)
        spans = tracer.drain()
        queued = {s.parent_id: s for s in _by_name(spans, "request.queued")}
        windows = _by_name(spans, "request.window")
        assert len(windows) == 3
        for w in windows:
            q = queued[w.parent_id]
            assert q.start_s <= w.start_s <= w.end_s == q.end_s
        (first, *_) = sorted(_by_name(spans, "frontend.idle"),
                             key=lambda s: s.start_s)
        assert first.parent_id is None
        # the wait ends only once a request has arrived
        assert first.end_s >= min(q.start_s for q in queued.values())


class TestHostSpans:
    def test_forced_collection_records_host_gc(self, traced):
        import gc
        from repro.obs import host
        assert host._on_gc in gc.callbacks
        heap = [{"i": i, "t": (i, str(i))} for i in range(600_000)]
        gc.collect()
        del heap
        spans = tracer.drain()
        collections = _by_name(spans, "host.gc")
        assert collections
        assert all(s.duration_s >= host.GC_MIN_S for s in collections)
        assert 2 in {s.tags["generation"] for s in collections}

    def test_gc_callbacks_unchanged_while_off(self):
        import gc
        configure(enabled=False)
        before = list(gc.callbacks)
        heap = [{"i": i} for i in range(100_000)]
        gc.collect()
        del heap
        assert gc.callbacks == before
        assert tracer.spans() == []

    def test_new_jit_records_jax_compile(self, traced):
        import random
        import jax
        import jax.numpy as jnp
        c = random.random()              # a program no cache holds
        with tracer.start_trace("request"):
            jax.jit(lambda x: x * c + 1.0)(jnp.ones(3)).block_until_ready()
        spans = tracer.drain()
        (root,) = _by_name(spans, "request")
        compiles = _by_name(spans, "jax.compile")
        assert compiles
        assert all(s.parent_id == root.span_id for s in compiles)
        assert all(s.end_s >= s.start_s for s in compiles)

    def test_tracing_off_adds_no_span_callback_or_annotation(self, tmp_path):
        import gc
        import jax
        import jax.numpy as jnp
        from jax._src import monitoring
        from repro.obs import host
        configure(enabled=True)
        configure(enabled=False)
        assert host._on_gc not in gc.callbacks
        assert host._on_duration not in \
            monitoring.get_event_duration_listeners()
        assert tracer.mirror("x") is None
        with tracer.span("x") as s:
            assert s is NOOP_SPAN
        svc = SynthesisService(tech=TECH, resolution=3,
                               cache=FrontierCache(store_dir=tmp_path))
        with ServiceFrontend(svc, window=0.01) as front:
            front.serve([SynthesisRequest(spec=spec_variants(1, seed=34)[0])],
                        timeout=600)
        jax.jit(lambda x: x - 2.5)(jnp.ones(2)).block_until_ready()
        gc.collect()
        assert tracer.spans() == []

    def test_spans_enter_profiler_annotations_while_on(self, traced,
                                                       monkeypatch):
        entered = []

        class Annotation:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                entered.append(("enter", self.name))

            def __exit__(self, *exc):
                entered.append(("exit", self.name))

        monkeypatch.setattr(tracer, "_annotation", Annotation)
        with tracer.start_trace("request"):
            with tracer.span("cache.mem"):
                pass
        tracer.start("request.queued",
                     parent=tracer.spans()[0].context).finish()
        assert entered == [("enter", "request"), ("enter", "cache.mem"),
                           ("exit", "cache.mem"), ("exit", "request")]
