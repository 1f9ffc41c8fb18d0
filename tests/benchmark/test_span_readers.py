"""The readers of the spans inside ``engine.execute`` and ``engine.extract``
and of the host's collections, on synthetic runs; a guard that the child
spans leave the older readers' values alone; the program's spans on the
profiler's clock; and discovery of the four-chip sweep cell."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

import discovery  # noqa: E402
import tracefile  # noqa: E402
from runrecord import Run  # noqa: E402


class _Span:
    def __init__(self, name, start, end, **tags):
        self.name, self.start_s, self.end_s, self.tags = name, start, end, tags

    @property
    def duration_s(self):
        return self.end_s - self.start_s


def _run(spans, t0=100.0, seconds=10.0, trace=None):
    run = Run(t0=t0, t_end=t0 + seconds, setup_s=1.0,
              gave_up=t0 + seconds + 60.0, spans=list(spans))
    run.trace = trace
    return run


# Two passes in the window (6 lanes), one before it; the older spans only.
_PARENT_SPANS = [
    _Span("engine.pass", 100.2, 104.0),
    _Span("engine.plan", 100.5, 101.0),
    _Span("engine.execute", 101.0, 103.0, n_specs=4),
    _Span("engine.extract", 103.0, 103.5, slot=0),
    _Span("engine.execute", 104.0, 105.0, n_specs=2),
    _Span("engine.extract", 105.0, 106.0, slot=0),
    _Span("engine.execute", 99.0, 100.0, n_specs=8),          # before
    _Span("request", 100.0, 101.0), _Span("request", 100.0, 102.0),
    _Span("request.queued", 100.0, 100.25),
    _Span("request.queued", 100.0, 100.75),
    _Span("cache.mem", 100.0, 100.5),
]

# What this program adds inside and beside them.
_CHILD_SPANS = [
    _Span("execute.pack", 101.0, 101.1),
    _Span("execute.wait", 101.2, 101.3),
    _Span("execute.fetch", 101.3, 101.9, arrays=40, bytes=1 << 20),
    _Span("execute.unpack", 101.9, 102.5),
    _Span("execute.fetch", 104.1, 104.4, arrays=40, bytes=1 << 19),
    _Span("extract.mask", 103.0, 103.3, rows=9000, where="host"),
    _Span("extract.mask", 105.0, 105.6, rows=9000, where="devices"),
    _Span("store.put", 103.3, 103.4, tiers="mem,disk"),
    _Span("store.put", 106.0, 106.3, tiers="mem,disk"),
    _Span("request.window", 100.1, 100.25),
    _Span("frontend.idle", 99.5, 100.0),
    _Span("host.gc", 99.9, 100.1, generation=2),
    _Span("host.gc", 103.0, 103.2, generation=1),
    _Span("host.gc", 109.9, 110.3, generation=2),
    _Span("jax.compile", 101.0, 101.05, fun_name="f"),
]


@pytest.fixture
def small():
    return json.loads((Path(__file__).resolve().parent / "data"
                       / "small_digest.json").read_text())


def test_new_readers_on_a_synthetic_run(small):
    run = _run(_PARENT_SPANS + _CHILD_SPANS, trace=small)
    assert run.lanes() == 6
    assert discovery.reader("fetch_s_per_lane.search")(run) == \
        pytest.approx(0.9 / 6)
    assert discovery.reader("mask_s_per_lane.sweep")(run) == \
        pytest.approx(0.9 / 6)
    assert discovery.reader("store_put_s_per_lane.sweep")(run) == \
        pytest.approx(0.4 / 6)
    # collections clipped to the window: 0.1 + 0.2 + 0.1 s of 10 s
    assert discovery.reader("gc_share.sweep")(run) == pytest.approx(4.0)


def test_new_readers_say_nothing_without_their_spans(small):
    bare = _run(_PARENT_SPANS, trace=small)
    for name in ("fetch_s_per_lane.search", "mask_s_per_lane.sweep",
                 "store_put_s_per_lane.sweep"):
        assert discovery.reader(name)(bare) is None
    # a program that records collections and saw none reads 0
    assert discovery.reader("gc_share.sweep")(bare) == 0.0
    # the CPU backend copies nothing: no device trace, no fetch reading
    untraced = _run(_PARENT_SPANS + _CHILD_SPANS, trace=None)
    assert discovery.reader("fetch_s_per_lane.search")(untraced) is None


@pytest.mark.parametrize("name", ["extract_s_per_lane.sweep",
                                  "execute_s_per_lane.sweep",
                                  "execute_s_per_lane.search",
                                  "plan_s_per_lane.search",
                                  "cache_probe_s.search",
                                  "queue_wait_s.search"])
def test_older_readers_ignore_the_child_spans(name):
    read = discovery.reader(name)
    before = read(_run(_PARENT_SPANS))
    after = read(_run(_PARENT_SPANS + _CHILD_SPANS))
    assert before is not None and after == before


_PROFILED = textwrap.dedent("""
    import json, sys, tempfile, time
    sys.path.insert(0, "benchmarks/chip")
    import jax
    import tracefile
    from jax.profiler import ProfileData
    from repro import obs

    trace_dir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    obs.configure(enabled=True)
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(tracefile.SYNC):
        sync_mono = time.monotonic()
    with obs.tracer.start_trace("probe.root"):
        for i in range(5):
            with obs.tracer.span(f"probe.child{i}"):
                time.sleep(0.01)
    jax.profiler.stop_trace()
    spans = obs.tracer.drain()
    obs.configure(enabled=False)
    data = ProfileData.from_file(str(tracefile.find_xplane(trace_dir)))
    events = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    events.setdefault(e.name, []).append(e.start_ns)
    offset = events[tracefile.SYNC][0] - sync_mono * 1e9
    print(json.dumps([[s.name, s.start_s * 1e9 + offset,
                       events.get(s.name, [])] for s in spans]))
""")


def test_mirrored_annotations_share_the_trace_clock():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(REPO / "src")}
    r = subprocess.run([sys.executable, "-c", _PROFILED], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    rows = json.loads(r.stdout.strip().splitlines()[-1])
    assert len(rows) == 6
    for name, mapped_ns, starts in rows:
        assert len(starts) == 1, name
        assert abs(starts[0] - mapped_ns) < 1e6, name


def test_four_chip_sweep_cell_is_discovered():
    bench = discovery.load_benchmark(REPO)
    wl = discovery.workload(bench, "sweep-full-x4.bulk")
    assert wl["chips"] == 4 and wl["traffic"] == "bulk-closed"
    cfg = discovery.config(bench, REPO, wl["config"])
    assert cfg["name"] == "syndcim-full-x4"
    assert cfg["service_mode"] == "auto"
    assert discovery.traffic(wl["traffic"])["clients"]["4"] == 8
    e2e = {m["name"] for m in discovery.metrics(bench, "end_to_end",
                                                wl["name"])}
    assert e2e == {"sweep_points_per_s", "setup_s"}
    per_layer = {m["name"] for m in discovery.metrics(bench, "per_layer",
                                                      wl["name"])}
    assert per_layer == {"execute_s_per_lane.sweep",
                         "extract_s_per_lane.sweep",
                         "rollup_device_s_per_lane.sweep",
                         "device_idle_share.sweep", "mask_s_per_lane.sweep",
                         "store_put_s_per_lane.sweep", "gc_share.sweep"}
    # the one-chip sweep cell reads the same layers
    assert per_layer == {m["name"] for m in discovery.metrics(
        bench, "per_layer", "sweep-full.bulk")}
    assert "fetch_s_per_lane.search" in {m["name"] for m in discovery.metrics(
        bench, "per_layer", "search-seed.open")}
