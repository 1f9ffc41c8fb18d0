"""The chip benchmark's yardstick on the CPU: trace reduction, the seeded
generators, the latency and rate arithmetic, discovery by name, and the
reference comparison.  No run of the service here (see
``test_bench_runs.py``)."""

import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmarks" / "chip"
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(BENCH))

import compare  # noqa: E402
import discovery  # noqa: E402
import tracefile  # noqa: E402
from runrecord import Request, Run  # noqa: E402
from traffic import Traffic, poisson_gaps  # noqa: E402


def _load(name):
    return json.loads((DATA / name).read_text())


# -- trace reduction ---------------------------------------------------------


@pytest.fixture
def small():
    return _load("small_digest.json")


def test_union_merges_and_clips(small):
    ops = small["devices"]["0"]["ops"]
    assert tracefile.union(ops, 50, 450) == [(100, 180), (300, 400)]
    assert tracefile.union(ops, 150, 350) == [(150, 180), (300, 350)]


def test_busy_and_idle_share(small):
    assert tracefile.busy_ns(small, 50, 450) == {"0": 180, "1": 400}
    assert tracefile.idle_share(small, 50, 450) == pytest.approx(0.275)
    assert tracefile.idle_share({"devices": {}, "host": []}, 0, 1) is None


def test_module_time_and_missing_program(small):
    assert tracefile.module_ns(small, "_eval_kernel", 50, 450) == 80
    assert tracefile.module_ns(small, "_eval_kernel", 200, 450) is None
    assert tracefile.module_ns(small, "no_such_program", 0, 1000) is None


def test_gaps_and_breakdown(small):
    assert tracefile.gaps(small, 50, 450) == [
        ("0", 50, 100), ("0", 180, 300), ("0", 400, 450)]
    spans = [("engine.pass", 150, 320), ("engine.extract", 200, 260),
             ("request.queued", 0, 120)]
    b = tracefile.breakdown(small, 50, 450, spans)
    assert b["device_ops"][0] == ["fusion.a", pytest.approx(225e-9)]
    assert [name for name, _ in b["device_ops"]] == [
        "fusion.a", "copy.c", "fusion.b"]
    assert b["idle_gaps"][0] == ["engine.extract", pytest.approx(120e-9)]
    assert b["idle_gaps"][1][0] == "request.queued"     # gap 50..100
    # a pass's own span names a gap before a waiting request's span does
    assert tracefile.label_at(spans + [("request.queued", 200, 201)],
                              200.5) == "engine.extract"
    assert len(b["idle_gaps"]) == 3
    assert tracefile.label_at(spans, 1000) == "no span open"


def test_sync_annotation(small):
    assert tracefile.sync_ns(small) == 10
    with pytest.raises(ValueError):
        tracefile.sync_ns({"devices": {}, "host": []})


def test_recorded_chip_digest_is_consistent():
    """A digest recorded on a TPU v5e by a ``--trace 1`` run: every device
    busy share lies in [0, 1], the roll-up program is found, and the longest
    gaps are each shorter than the window."""
    dig = _load("chip_digest.json")
    lo, hi = dig["window_ns"]
    busy = tracefile.busy_ns(dig, lo, hi)
    assert busy and all(0 < b <= hi - lo for b in busy.values())
    share = tracefile.idle_share(dig, lo, hi)
    assert 0.0 <= share < 1.0
    assert tracefile.module_ns(dig, "_eval_kernel", lo, hi) > 0
    b = tracefile.breakdown(dig, lo, hi, [])
    assert 0 < len(b["device_ops"]) <= 10
    assert all(0 < s <= (hi - lo) / 1e9 for _, s in b["idle_gaps"])


# -- generators --------------------------------------------------------------


def _cfg(name):
    bench = discovery.load_benchmark(REPO)
    return discovery.config(bench, REPO, name)


@pytest.mark.parametrize("mix", ["open-miss", "open-hot"])
def test_open_schedule_is_seeded(mix):
    cfg, trf = _cfg("syndcim-seed"), discovery.traffic(mix)
    a = Traffic(cfg, trf, 2**31 + 12345).schedule(20.0)
    b = Traffic(cfg, trf, 2**31 + 12345).schedule(20.0)
    c = Traffic(cfg, trf, 7).schedule(20.0)
    assert a == b
    assert a != c
    # the same arrivals in another order: same count, same last due time
    assert len(a) == len(c)
    assert a[-1].due_s == pytest.approx(c[-1].due_s)
    assert all(x.due_s < y.due_s for x, y in zip(a, a[1:]))
    assert a[-1].due_s < 20.0


def test_open_miss_specs_never_repeat():
    cfg, trf = _cfg("syndcim-seed"), discovery.traffic("open-miss")
    t = Traffic(cfg, trf, 99)
    arrivals = t.schedule(30.0)
    keys = [tuple(sorted(a.spec.items())) for a in arrivals]
    keys += [tuple(sorted(s.items())) for s in t.warm]
    assert len(set(keys)) == len(keys)
    assert not any(a.hot for a in arrivals)


def test_open_hot_share_and_pool():
    cfg, trf = _cfg("syndcim-seed"), discovery.traffic("open-hot")
    t = Traffic(cfg, trf, 5)
    arrivals = t.schedule(30.0)
    hot = [a for a in arrivals if a.hot]
    assert len(hot) == round(trf["hot_share"] * len(arrivals))
    pool = [tuple(sorted(s.items())) for s in t.pool]
    assert len(set(pool)) == len(pool) == trf["pool_size"]
    assert all(tuple(sorted(a.spec.items())) in pool for a in hot)
    fresh = [tuple(sorted(a.spec.items())) for a in arrivals if not a.hot]
    assert len(set(fresh)) == len(fresh)
    assert not set(fresh) & set(pool)
    # Zipf(1.1) over the pool: the most popular spec is asked most
    counts = {}
    for a in hot:
        k = tuple(sorted(a.spec.items()))
        counts[k] = counts.get(k, 0) + 1
    assert counts.get(pool[0], 0) == max(counts.values())


def test_spec_space_bounds():
    cfg = _cfg("syndcim-seed")
    t = Traffic(cfg, discovery.traffic("open-miss"), 11)
    for _ in range(300):
        s = t.specs.fresh()
        assert 250e6 <= s["f_wupdate_hz"] <= s["f_mac_hz"] <= 1100e6
        assert s["vdd"] in cfg["spec_space"]["vdd"]
        assert s["mcr"] in (1, 2, 4)
        assert {s["w_power"], s["w_area"], s["w_throughput"]} <= {
            0.5, 1.0, 2.0}
        assert (s["h"], s["w"]) == (64, 64)


def test_closed_stream_same_blocks_in_seeded_order():
    cfg = _cfg("syndcim-full")
    trf = discovery.traffic("bulk-closed")

    def blocks(seed, n=5, c=4):
        it = Traffic(cfg, trf, seed).closed_stream(c)
        return [[next(it) for _ in range(c)] for _ in range(n)]

    a, b, c = blocks(3), blocks(3), blocks(2**31 + 9)
    assert a == b and a != c
    key = lambda s: tuple(sorted(s.items()))  # noqa: E731
    # every seed serves the same specs in each pass, in its own order
    assert [sorted(map(key, x)) for x in a] == \
        [sorted(map(key, x)) for x in c]
    flat = [key(s) for x in a for s in x]
    assert len(set(flat)) == len(flat)
    assert "w_power" not in a[0][0]
    t = Traffic(cfg, trf, 3)
    assert t.clients(1) == 4 and t.clients(4) == 8


def test_poisson_gaps_fixed_by_arrival_seed():
    g1, g2 = poisson_gaps(50.0, 10.0, 1), poisson_gaps(50.0, 10.0, 1)
    assert (g1 == g2).all() and g1.sum() < 10.0
    assert 350 < len(g1) < 650


# -- latency and rate arithmetic ----------------------------------------------


def _run(requests, t0=100.0, seconds=10.0):
    return Run(t0=t0, t_end=t0 + seconds, setup_s=1.0,
               gave_up=t0 + seconds + 60.0, requests=requests)


class _Served:
    served_from = "engine"


class _Shed:
    reason = "queue_full"


def test_latency_counts_from_due_time():
    reqs = [Request(spec={}, due=100.0 + i, done=100.0 + i + 0.1 * (i + 1),
                    response=_Served()) for i in range(10)]
    run = _run(reqs)
    lat = run.latencies()
    assert lat == pytest.approx([0.1 * (i + 1) for i in range(10)])
    assert run.quantile(0.5) == pytest.approx(0.55)
    assert run.quantile(0.95) == pytest.approx(0.955)


def test_failed_and_late_requests_count_with_their_wait():
    reqs = [Request(spec={}, due=101.0, done=101.5, response=_Served()),
            Request(spec={}, due=102.0, response=_Shed()),
            Request(spec={}, due=103.0),                      # never came
            Request(spec={}, due=111.0, done=111.2, response=_Served())]
    run = _run(reqs)
    assert run.latencies() == pytest.approx([0.5, 68.0, 67.0])
    assert [r.failed for r in reqs] == [False, True, True, False]


def test_sweep_rate_over_completed_work():
    read = discovery.reader("sweep_points_per_s")
    reqs = [Request(spec={}, due=100.0, done=104.0, points=1000,
                    response=_Served()),
            Request(spec={}, due=100.0, done=108.0, points=1000,
                    response=_Served()),
            # still in flight at the close: left out, not a partial count
            Request(spec={}, due=104.0, done=112.0, points=1000,
                    response=_Served()),
            Request(spec={}, due=108.0, response=_Shed())]
    assert read(_run(reqs)) == pytest.approx(2000 / 8.0)
    assert read(_run(reqs[2:])) is None


class _Span:
    def __init__(self, name, start, end, **tags):
        self.name, self.start_s, self.end_s, self.tags = name, start, end, tags

    @property
    def duration_s(self):
        return self.end_s - self.start_s


def test_span_readers_per_lane_and_per_request():
    run = _run([])
    run.spans = [_Span("engine.execute", 101, 103, n_specs=4),
                 _Span("engine.execute", 104, 105, n_specs=2),
                 _Span("engine.plan", 100.5, 101, ),
                 _Span("engine.execute", 99, 100, n_specs=8),   # before
                 _Span("request", 100, 101), _Span("request", 100, 102),
                 _Span("request.queued", 100, 100.25),
                 _Span("request.queued", 100, 100.75),
                 _Span("cache.mem", 100, 100.5)]
    assert run.lanes() == 6
    assert discovery.reader("execute_s_per_lane.search")(run) == \
        pytest.approx(3.0 / 6)
    assert discovery.reader("plan_s_per_lane.search")(run) == \
        pytest.approx(0.5 / 6)
    assert discovery.reader("queue_wait_s.search")(run) == pytest.approx(0.5)
    assert discovery.reader("cache_probe_s.search")(run) == \
        pytest.approx(0.25)
    # no trace: the device readers find nothing and say so
    assert discovery.reader("device_idle_share.search")(run) is None
    assert discovery.reader("rollup_device_s_per_lane.sweep")(run) is None


def test_device_readers_on_a_trace(small):
    run = _run([], t0=0.0, seconds=400e-9)
    run.trace, run.trace_offset_ns = small, 50.0
    run.spans = [_Span("engine.execute", 0.0, 1e-7, n_specs=2)]
    assert discovery.reader("device_idle_share.sweep")(run) == \
        pytest.approx(27.5)
    assert discovery.reader("rollup_device_s_per_lane.sweep")(run) == \
        pytest.approx(80e-9 / 2)


# -- discovery by name ---------------------------------------------------------


def test_every_named_piece_has_its_file():
    bench = discovery.load_benchmark(REPO)
    for c in bench["configs"]:
        assert discovery.config(bench, REPO, c["name"])["name"] == c["name"]
    for w in bench["workloads"]:
        discovery.traffic(w["traffic"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(discovery.reader(m["name"]))
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        target = next(e for e in bench["end_to_end"]
                      if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(target.get("workloads", cells))


def test_new_pieces_are_found_without_editing_any_file(tmp_path):
    """A later change adds a configuration, a traffic mix and a metric as
    new files plus entries, and discovery finds them."""
    here = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH, here)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    cfg = json.loads((here / "configs" / "syndcim-seed.json").read_text())
    cfg["name"] = "syndcim-new"
    (here / "configs" / "syndcim-new.json").write_text(json.dumps(cfg))
    trf = json.loads((here / "traffic" / "open-miss.json").read_text())
    trf["rate_per_s"] = 3.0
    (here / "traffic" / "slow-miss.json").write_text(json.dumps(trf))
    (here / "metrics" / "requests_seen.py").write_text(
        "def read(run):\n    return len(run.requests)\n")
    bench["configs"].append({"name": "syndcim-new", "source": "x",
                             "file": "benchmarks/chip/configs/"
                                     "syndcim-new.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "new.slow", "config": "syndcim-new",
                               "traffic": "slow-miss", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "requests_seen", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "frontend", "moves": "search_p50_s",
                               "workloads": ["new.slow"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    b = discovery.load_benchmark(tmp_path)
    wl = discovery.workload(b, "new.slow")
    assert discovery.config(b, tmp_path, wl["config"])["name"] == \
        "syndcim-new"
    assert discovery.traffic(wl["traffic"], here)["rate_per_s"] == 3.0
    names = [m["name"] for m in discovery.metrics(b, "per_layer",
                                                  "new.slow")]
    assert names == ["requests_seen"]
    assert discovery.reader("requests_seen", here)(_run([])) == 0
    assert all(p.read_bytes() == data for p, data in before.items())
    with pytest.raises(KeyError):
        discovery.workload(b, "no.such.cell")


# -- the reference comparison ----------------------------------------------------


def _answers():
    sys.path.insert(0, str(REPO / "src"))
    from repro.core.macro import MacroSpec, calibrated_tech_for_reference
    from repro.core.searcher import mso_search
    from reference import lattice as L
    from reference import macro as RM
    fields = dict(h=64, w=64, mcr=2, int_precisions=(4, 8),
                  fp_precisions=("FP4", "FP8"), f_mac_hz=600e6,
                  f_wupdate_hz=400e6, vdd=0.8, w_power=2.0)
    got = mso_search(MacroSpec(**fields), None,
                     calibrated_tech_for_reference(), resolution=4)
    ref = L.search(RM.MacroSpec(**fields), RM.calibrated_tech_for_reference(),
                   4)
    return got, ref


def test_reference_agrees_with_the_program_on_the_cpu():
    got, ref = _answers()
    cmp = compare.Comparison()
    cmp.add_answer("search", got, ref)
    assert cmp.correct()
    assert cmp.points == len(ref.frontier) + len(ref.explored) > 0
    assert cmp.ppa_max_rel == 0.0


def test_comparison_catches_order_fields_and_values():
    import dataclasses
    got, ref = _answers()
    cmp = compare.Comparison()
    cmp.add_answer("reversed", dataclasses.replace(
        got, explored=tuple(reversed(got.explored))), ref)
    assert cmp.order_mismatches == 1 and not cmp.correct()

    p = got.frontier[0]
    cmp = compare.Comparison()
    cmp.add_point(dataclasses.replace(p, latency_cycles=p.latency_cycles + 1),
                  ref.frontier[0])
    assert cmp.field_mismatches == 1 and not cmp.correct()

    cmp = compare.Comparison()
    cmp.answers = 1
    cmp.add_point(dataclasses.replace(p, area_um2=p.area_um2 * (1 + 1e-7)),
                  ref.frontier[0])
    assert cmp.ppa_max_rel == pytest.approx(1e-7, rel=1e-3)
    assert cmp.worst_field == "area_um2" and not cmp.correct()
    checks = cmp.checks()
    assert list(checks) == ["order_mismatches", "field_mismatches",
                            "ppa_max_rel"]
    assert checks["ppa_max_rel"]["limit"] == compare.PPA_MAX_REL
