"""Request populations beyond one macro family under one tech: spec spaces
with several macro families, and sweeps re-asked under a recalibrated tech
(``recal``).  Also the guard that the older cells still draw, warm and
count exactly what they drew before either was added."""

import ast
import hashlib
import itertools
import json
import re
import sys
from pathlib import Path

import pytest

from test_bench_runs import TWO_FAMILIES, _cell, checkout  # noqa: F401

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

import discovery  # noqa: E402
import harness  # noqa: E402
from runrecord import Request, Run  # noqa: E402
from traffic import SpecSpace, Traffic  # noqa: E402


def _cfg(name):
    bench = discovery.load_benchmark(REPO)
    return discovery.config(bench, REPO, name)


def _key(spec):
    return tuple(sorted(spec.items()))


# -- the older cells draw as before -------------------------------------------

#: sha256 of each cell's set-up specs, first 64 window specs (with due
#: times and hot flags in an open loop), warm-up specs, warmed lane counts
#: and the points each window spec counts, as the harness computed them
#: before spec spaces took families and closed loops took ``recal``.
PARENT_DRAWS = {
    ("sweep-full.bulk", 7):
        "48f742232014d5ca53f758c6113fc947841fc211f33652774f977418e25621cf",
    ("sweep-full.bulk", 2**31 + 3):
        "4fdaaf492ef03d9425be8f805c843822136cef6d73bbcbda2caafe4b290b12fd",
    ("search-seed.open", 7):
        "ac3927dfffad0a4b27fe311c0e6bc4882761a3d4fbdbacf39e336565b5a6f663",
    ("search-seed.open", 2**31 + 3):
        "35a406a82d55d0726ae702d0ceac5cdfa073a6c5c60228a2063c4400ecd27ad1",
    ("search-seed.hot", 7):
        "455c089371c17f7eca862c58dc46ae2f5f10687640e1787227bb0af5f1a24683",
    ("search-seed.hot", 2**31 + 3):
        "5458d48107d34da2e5904d12c93bf4ac190953fc5ee6372443aff9ddc2cdbe3f",
    ("sweep-full-x4.bulk", 7):
        "6a7fb4c659976fbc2018c01984899bf5df870e72d435957470ae0fd8ee73379e",
    ("sweep-full-x4.bulk", 2**31 + 3):
        "6bef37b8d8a939e78396abf470db37da375be31b38506d84e745f3a5350e95f7",
}

PARENT_POINTS = {"sweep-full.bulk": 155520, "search-seed.open": 17280,
                 "search-seed.hot": 17280, "sweep-full-x4.bulk": 155520}


@pytest.mark.parametrize("cell,seed", sorted(PARENT_DRAWS))
def test_older_cells_draw_as_before(cell, seed):
    bench = discovery.load_benchmark(REPO)
    wl = discovery.workload(bench, cell)
    cfg = discovery.config(bench, REPO, wl["config"])
    t = Traffic(cfg, discovery.traffic(wl["traffic"]), seed)
    _, lane_counts = harness.batching(t, int(wl["chips"]))
    points = harness.lattice_points(cfg, t.families)
    if t.loop == "closed":
        pairs = list(itertools.islice(t.stream(lane_counts[-1]), 64))
        assert all(recal is None for _, recal in pairs)
        specs = stream = [spec for spec, _ in pairs]
    else:
        arrivals = t.schedule(50.0)[:64]
        stream = [[a.due_s, a.spec, a.hot] for a in arrivals]
        specs = [a.spec for a in arrivals]
    record = {"setup": t.warm + t.pool if t.loop == "open" else [],
              "stream": stream,
              "warm_engine": [t.warm_specs(max(lane_counts), k)
                              for k in range(len(t.families))],
              "lane_counts": lane_counts,
              "points": [points[s["h"], s["w"]] for s in specs]}
    blob = json.dumps(record, sort_keys=True).encode()
    assert hashlib.sha256(blob).hexdigest() == PARENT_DRAWS[cell, seed]
    assert set(points.values()) == {PARENT_POINTS[cell]}


# -- macro families -----------------------------------------------------------


def _mixed_space():
    space = dict(_cfg("syndcim-seed")["spec_space"])
    for k in ("h", "w", "int_precisions", "fp_precisions"):
        del space[k]
    return {**space, "families": TWO_FAMILIES}


def test_families_are_drawn_by_share_from_one_unique_stream():
    space = _mixed_space()
    space["families"] = [dict(TWO_FAMILIES[0], share=3),
                         dict(TWO_FAMILIES[1], share=1)]
    s = SpecSpace(space, 2**31 + 21, 1)
    specs = [s.fresh() for _ in range(800)]
    assert len({_key(x) for x in specs}) == len(specs)
    big = sum(x["h"] == 64 for x in specs)
    assert 540 < big < 660                  # 3:1 by share
    small = next(x for x in specs if x["h"] == 32)
    assert (small["w"], small["int_precisions"], small["fp_precisions"]) \
        == (64, (8,), ("FP8",))
    # a forced family skips the pick; the posture draws stay in step
    a, b = SpecSpace(space, 5, 1), SpecSpace(space, 5, 1)
    x, y = a.fresh(0), b.fresh(1)
    assert (x["h"], y["h"]) == (64, 32)
    assert {k: v for k, v in x.items() if k not in ("h", "int_precisions",
                                                    "fp_precisions")} == \
        {k: v for k, v in y.items() if k not in ("h", "int_precisions",
                                                 "fp_precisions")}


def test_each_family_is_warmed_served_and_counted():
    cfg = _cfg("syndcim-seed")
    cfg["spec_space"] = _mixed_space()
    t = Traffic(cfg, discovery.traffic("open-miss"), 3)
    assert [s["h"] for s in t.warm] == [64, 32]
    assert {s["h"] for s in t.warm_specs(4, 1)} == {32}
    max_batch, lanes = harness.batching(t, 1)
    assert max_batch == 32 and lanes == list(range(1, 33))
    # each geometry counts its own lattice (an 8-row macro has fewer splits)
    points = harness.lattice_points(
        cfg, t.families + [dict(TWO_FAMILIES[1], h=8)])
    assert points == {(64, 64): 17280, (32, 64): 17280, (8, 64): 11520}
    # a closed loop's pass of C lanes splits into groups of any size
    closed = Traffic(cfg, discovery.traffic("bulk-closed"), 3)
    assert harness.batching(closed, 1) == (4, [1, 2, 3, 4])


@pytest.mark.parametrize("change,message", [
    ({"h": 48}, "powers of two"),
    ({"fp_precisions": ["FP5"]}, "unknown FP formats"),
    ({"share": 0}, "share must be positive"),
    ({"int_precisions": []}, "at least one INT precision"),
    ({"mcr": 2}, "has the keys"),
])
def test_a_bad_family_is_an_error_at_load(change, message):
    space = _mixed_space()
    space["families"] = [TWO_FAMILIES[0], {**TWO_FAMILIES[1], **change}]
    cfg = {**_cfg("syndcim-seed"), "spec_space": space}
    with pytest.raises(ValueError, match=message):
        Traffic(cfg, discovery.traffic("open-miss"), 1)


def test_families_and_one_family_keys_do_not_mix():
    space = {**_mixed_space(), "h": 64}
    with pytest.raises(ValueError, match="not both"):
        SpecSpace(space, 1, 1)


# -- a recalibrated tech per request ------------------------------------------


def _recal_traffic(seed):
    return Traffic(_cfg("syndcim-full"), discovery.traffic("recal-closed"),
                   seed)


def test_recal_stream_blocks_factors_and_pool():
    a, b = _recal_traffic(2**31 + 1), _recal_traffic(17)
    pool, beside = a.recal_specs()
    assert (pool, beside) == b.recal_specs()      # the same in every run
    assert len(pool) == 16 and _key(beside) not in {_key(s) for s in pool}
    factors = a.recal_factors()
    assert len(factors) == 160 and 1.0 not in factors and 0.5 not in factors
    assert min(factors) == 0.8 and max(factors) == 1.2
    pa, pb = list(a.stream(4)), list(b.stream(4))
    assert len(pa) == len({(_key(s), r) for s, r in pa}) == 2560
    assert pa != pb
    blocks = [pa[i:i + 4] for i in range(0, len(pa), 4)]
    other = [pb[i:i + 4] for i in range(0, len(pb), 4)]
    for x, y in zip(blocks, other):
        # one factor a block, the same specs in every seed's block
        assert len({r for _, r in x}) == 1 and x[0][1][0] == "a_sram6t"
        assert sorted(_key(s) for s, _ in x) == sorted(_key(s) for s, _ in y)
    # each factor in turn, in the same order in every run
    for i in range(0, len(pa), 16):
        assert len({r for _, r in pa[i:i + 16]}) == 1
    assert [r for _, r in pa[::16]] == [r for _, r in pb[::16]]
    with pytest.raises(ValueError, match="multiple"):
        next(a.stream(3))


class _Served:
    served_from = "engine"

    def __init__(self, result):
        self.result = result


def test_recalibrated_answer_is_judged_under_its_own_tech(tmp_path):
    """A sweep served under a rescaled 6T area passes against the reference
    under the same scale, and fails against the unscaled reference."""
    sys.path.insert(0, str(REPO / "src"))
    import sut
    cfg = _cfg("syndcim-seed")
    cfg["request"] = {**cfg["request"], "kind": "sweep"}
    dep = sut.Deployment(cfg)
    spec = _recal_traffic(1).recal_specs()[0][0]
    service = dep.service(tmp_path)
    resp, = service.serve([dep.request(
        spec, dep.recalibrated("a_sram6t", 1.1))])

    def judged(recal):
        r = Request(spec=spec, due=100.0, done=101.0, points=17280,
                    response=_Served(resp.result), recal=recal)
        run = Run(t0=100.0, t_end=110.0, setup_s=1.0, gave_up=170.0,
                  requests=[r])
        return harness.judge(cfg, run, 1)

    right = judged(("a_sram6t", 1.1))
    assert right.correct() and right.ppa_max_rel < 1e-12
    wrong = judged(None)
    assert not wrong.correct()
    assert wrong.ppa_max_rel > 1e-3 or wrong.order_mismatches > 0


# -- whole runs on the CPU ----------------------------------------------------


def _service_in_window(err):
    line = next(x for x in err.splitlines()
                if x.startswith("service in the window: "))
    m = re.match(r"service in the window: (\{.*\}); (\d+) of its requests "
                 r"served", line)
    return ast.literal_eval(m.group(1)), int(m.group(2))


def test_two_family_cell_runs_in_groups_and_is_correct(checkout):  # noqa: F811
    rc, res, err = _cell(checkout, "tiny.mixed", 2**31 + 41, "--trace", "1",
                         seconds="3")
    assert rc == 0, err[-3000:]
    assert res["correct"] is True, err[-3000:]
    assert res["attempted"] > 10
    assert "compiles in window: 0 programs lowered, 0 compiled" in err
    groups = re.search(r"at most (\d+) groups in a pass", err)
    assert groups and int(groups.group(1)) >= 2, err[-3000:]


def test_recal_cell_answers_every_sweep_incrementally(checkout):  # noqa: F811
    rc, res, err = _cell(checkout, "tiny.recal", 2**31 + 43, "--trace", "0",
                         seconds="6")
    assert rc == 0, err[-3000:]
    assert res["correct"] is True, err[-3000:]
    assert res["metrics"]["sweep_points_per_s"]["value"] > 0
    assert "compiles in window: 0 programs lowered, 0 compiled" in err
    delta, served = _service_in_window(err)
    assert served >= 4
    assert delta["incremental_passes"] == served
    assert delta["fused_passes"] == 0 and delta["cache_hits"] == 0


def test_recal_cell_traced_reads_its_layers(checkout):  # noqa: F811
    rc, res, err = _cell(checkout, "tiny.recal", 2**31 + 45, "--trace", "1",
                         seconds="6")
    assert rc == 0, err[-3000:]
    assert res["correct"] is True, err[-3000:]
    # the CPU has no device trace: the device readers say nothing
    assert set(res["metrics"]) == {
        "incremental_s_per_sweep.recal", "cache_probe_s.recal",
        "execute_s_per_lane.sweep", "mask_s_per_lane.sweep",
        "gc_share.sweep"}
    assert all(m["value"] >= 0 for m in res["metrics"].values())
    assert res["metrics"]["incremental_s_per_sweep.recal"]["value"] > 0


@pytest.mark.parametrize("fault", ["f32", "alter"])   # the control first
def test_broken_recal_cell_is_not_correct(checkout, fault):  # noqa: F811
    rc, res, err = _cell(checkout, "tiny.recal", 3, "--trace", "0",
                         "--fault", fault, seconds="4")
    assert rc == 0, err[-3000:]
    assert res["correct"] is False
    assert res["checks"]["ppa_max_rel"]["value"] > \
        res["checks"]["ppa_max_rel"]["limit"]
