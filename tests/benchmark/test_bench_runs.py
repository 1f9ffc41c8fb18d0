"""Whole runs of the chip benchmark's harness on the CPU, at a size a test
run holds: the rehearsal path (``harness.run(require_chip=False)``) drives
the real frontend, service and engine, the reference comparison and the
result line.  Each run is its own process, as on the chip, and the sharded
path of the four-chip cell runs on two virtual CPU devices (the service
shards a pass at two lanes per device, so four lanes suffice there).

Also the controls: with the timed path broken underneath (``faults.py``),
``correct`` comes out false; and the harness refuses to run without a TPU,
or without the program beside it.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmarks" / "chip"

REHEARSE = ("import sys; sys.path.insert(0, 'benchmarks/chip'); "
            "import harness; "
            "sys.exit(harness.run(sys.argv[1:], require_chip=False))")


def _tiny_config(kind: str) -> dict:
    cfg = json.loads((BENCH / "configs" / "syndcim-seed.json").read_text())
    cfg["name"] = f"tiny-{kind}"
    cfg["request"]["kind"] = kind
    if kind == "sweep":
        cfg["request"]["priority"] = "BULK"
    return cfg


#: Two macro families that the engine cannot fuse into one group: their
#: split axes and their precision-mode counts differ.
TWO_FAMILIES = [
    {"h": 64, "w": 64, "int_precisions": [4, 8],
     "fp_precisions": ["FP4", "FP8"], "share": 0.5},
    {"h": 32, "w": 64, "int_precisions": [8], "fp_precisions": ["FP8"],
     "share": 0.5}]


def _two_family_config() -> dict:
    cfg = _tiny_config("search")
    cfg["name"] = "tiny-mixed"
    space = cfg["spec_space"]
    for k in ("h", "w", "int_precisions", "fp_precisions"):
        del space[k]
    space["families"] = TWO_FAMILIES
    return cfg


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout holding the benchmark, the program and five tiny cells:
    searches on the seed lattice in an open loop of 4-lane batches, the
    same over two macro families, seed-lattice sweeps in a closed loop on
    one and on two devices, and the same sweeps re-asked under a
    recalibrated tech."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "benchmarks" / "chip")
    (root / "src").symlink_to(REPO / "src")
    here = root / "benchmarks" / "chip"
    for kind in ("search", "sweep"):
        (here / "configs" / f"tiny-{kind}.json").write_text(
            json.dumps(_tiny_config(kind)))
    (here / "configs" / "tiny-mixed.json").write_text(
        json.dumps(_two_family_config()))
    open_mix = json.loads((here / "traffic" / "open-miss.json").read_text())
    open_mix["rate_per_s"] = 20.0
    open_mix["frontend"]["max_batch"] = 4
    (here / "traffic" / "tiny-open.json").write_text(json.dumps(open_mix))
    closed = json.loads((here / "traffic" / "bulk-closed.json").read_text())
    closed["clients"] = {"1": 2, "2": 4}
    (here / "traffic" / "tiny-closed.json").write_text(json.dumps(closed))
    recal = json.loads((here / "traffic" / "recal-closed.json").read_text())
    recal["clients"] = {"1": 2}
    recal["recal"]["pool_size"] = 4
    (here / "traffic" / "tiny-recal.json").write_text(json.dumps(recal))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for kind in ("search", "sweep", "mixed"):
        bench["configs"].append({
            "name": f"tiny-{kind}", "source": "test", "reduced": [],
            "file": f"benchmarks/chip/configs/tiny-{kind}.json",
            "why": "test"})
    bench["workloads"] += [
        {"name": "tiny.search", "config": "tiny-search",
         "traffic": "tiny-open", "chips": 1, "why": "test"},
        {"name": "tiny.sweep", "config": "tiny-sweep",
         "traffic": "tiny-closed", "chips": 1, "why": "test"},
        {"name": "tiny.sweep-x2", "config": "tiny-sweep",
         "traffic": "tiny-closed", "chips": 2, "why": "test"},
        {"name": "tiny.mixed", "config": "tiny-mixed",
         "traffic": "tiny-open", "chips": 1, "why": "test"},
        {"name": "tiny.recal", "config": "tiny-sweep",
         "traffic": "tiny-recal", "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells = m.get("workloads", [])
        if "sweep-full.recal" in cells:
            cells.append("tiny.recal")
        if "sweep-full.bulk" in cells:
            cells += ["tiny.sweep", "tiny.sweep-x2"]
        if "search-seed.open" in cells:
            cells += ["tiny.search", "tiny.mixed"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _run(root, *args, devices: int = 1, code: str = REHEARSE):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(root / ".jax_cache")}
    env.pop("PYTHONPATH", None)
    if devices > 1:
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                            f"{devices}")
    r = subprocess.run([sys.executable, "-c", code, *args], cwd=root,
                       env=env, capture_output=True, text=True, timeout=600)
    last = r.stdout.strip().splitlines()[-1] if r.stdout.strip() else ""
    result = json.loads(last) if last.startswith("{") else None
    return r.returncode, result, r.stderr


def _cell(root, cell, seed, *extra, seconds="2", devices=1):
    return _run(root, "--workload", cell, "--seed", str(seed), "--seconds",
                seconds, *extra, devices=devices)


def test_result_line_schema(checkout):
    rc, res, err = _cell(checkout, "tiny.search", 2**31 + 7, "--trace", "0")
    assert rc == 0, err[-3000:]
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 10
    assert set(res["metrics"]) == {"search_p50_s", "setup_s"}
    assert res["metrics"]["search_p50_s"]["unit"] == "s"
    assert 0 < res["metrics"]["search_p50_s"]["value"] < 2.0
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert res["device"]["platform"] == "cpu"
    for name, c in res["checks"].items():
        assert set(c) == {"value", "limit"}
        assert f"check {name} = " in err
    # the compared numbers are the last lines of standard error
    assert err.strip().splitlines()[-1].startswith("check ppa_max_rel")
    assert "compiles in window: 0 programs lowered, 0 compiled" in err


def test_traced_run_reports_per_layer_metrics(checkout):
    rc, res, err = _cell(checkout, "tiny.search", 11, "--trace", "1")
    assert rc == 0, err[-3000:]
    assert res["correct"] is True
    per_layer = {"queue_wait_s.search", "cache_probe_s.search",
                 "plan_s_per_lane.search", "execute_s_per_lane.search"}
    # the CPU has no device trace: the device readers say nothing
    assert set(res["metrics"]) == per_layer
    assert all(m["value"] > 0 for m in res["metrics"].values())


# Windows long enough for a pass to complete on a CPU shared with other
# test workers.
@pytest.mark.parametrize("cell,devices,seconds", [("tiny.sweep", 1, "6"),
                                                  ("tiny.sweep-x2", 2, "8")])
def test_sweep_cells_are_correct(checkout, cell, devices, seconds):
    rc, res, err = _cell(checkout, cell, 5, "--trace", "0", seconds=seconds,
                         devices=devices)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True, err[-3000:]
    assert res["metrics"]["sweep_points_per_s"]["value"] > 0
    assert res["device"]["count"] == devices
    assert res["checks"]["order_mismatches"]["value"] == 0
    assert "compiles in window: 0 programs lowered, 0 compiled" in err


@pytest.mark.parametrize("cell,fault,devices,seconds", [
    ("tiny.search", "f32", 1, "2"),          # the control
    ("tiny.search", "alter", 1, "2"),
    ("tiny.sweep-x2", "exchange", 2, "8"),
])
def test_broken_timed_path_is_not_correct(checkout, cell, fault, devices,
                                          seconds):
    rc, res, err = _cell(checkout, cell, 3, "--trace", "0", "--fault",
                         fault, seconds=seconds, devices=devices)
    assert rc == 0, err[-3000:]
    assert res["correct"] is False
    c = res["checks"]
    assert (c["order_mismatches"]["value"] > 0
            or c["ppa_max_rel"]["value"] > c["ppa_max_rel"]["limit"])


def test_refuses_without_a_tpu(checkout):
    rc, res, err = _run(checkout, "benchmarks/chip/harness.py",
                        "--workload", "tiny.search", "--seed", "1",
                        "--seconds", "1", "--trace", "0",
                        code="import runpy, sys; sys.argv = sys.argv[1:]; "
                             "runpy.run_path(sys.argv[0], "
                             "run_name='__main__')")
    assert rc == 2
    assert res is None
    assert "no TPU" in err


def test_refuses_without_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's files."""
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip")
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    rc, res, _ = _run(tmp_path, "--workload", "search-seed.open", "--seed",
                      "1", "--seconds", "1", "--trace", "0")
    assert rc != 0 and res is None
