"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and its
phases hold at a tiny size (a small lattice, a five-lane burst, kernels in
interpret mode at small shapes), so the script keeps working between chip
runs."""

import importlib.util
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro import obs
from repro.core.axes import LatticeConfig
from repro.service import FrontierCache, ServiceFrontend, SynthesisService

REPO = Path(__file__).resolve().parent.parent


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cs = _load_chip_smoke()


@pytest.fixture
def traced():
    obs.configure(enabled=True, sample=1.0)
    obs.tracer.clear()
    yield
    obs.tracer.configure(enabled=False)
    obs.tracer.clear()


def test_refuses_without_tpu(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, str(REPO / "chip_smoke.py"),
                        "--out", str(tmp_path)],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout


def test_phases_at_tiny_size(tmp_path, traced):
    specs, config = cs.burst_specs(1), LatticeConfig()
    service = SynthesisService(cache=FrontierCache(store_dir=tmp_path / "c"))
    with ServiceFrontend(service, max_batch=len(specs), window=1.0) as front:
        resp = cs.cold_pass(service, front, specs, config)
        cs.oracle_check(service, front, specs, config, resp, seed=0,
                        n_points=20)
        cs.warm_pass(service, front, specs, config)
        cs.selection(front)
    cs.launcher(tmp_path)
    cs.kernels(interpret=True, dcim_shape=(16, 256, 256), ssm_shape=(64, 128),
               csa_shape=(40, 200))


def test_oracle_comparison_flags_a_drift():
    """A field that drifts past the bound fails the check; exact points
    pass."""
    from repro.core.batched import DesignLattice, SpecTables, evaluate
    from repro.core.macro import calibrated_tech_for_reference, rollup
    import dataclasses
    tech = calibrated_tech_for_reference()
    spec = cs.burst_specs(0)[0]
    lat = DesignLattice.enumerate(spec, config=LatticeConfig())
    i = int(lat.valid.nonzero()[0][0])
    ref = rollup(lat.design_at(i), tech)
    got = evaluate(lat, SpecTables(spec, tech, config=LatticeConfig())
                   ).materialize(i)
    cmp = cs.Comparison()
    cmp.add(got, ref)
    cmp.verdict("exact")
    cmp.add(dataclasses.replace(got, area_um2=got.area_um2 * (1 + 1e-6)),
            ref)
    with pytest.raises(cs.SmokeFailure, match="area_um2"):
        cmp.verdict("drift")


def test_four_chip_phase_on_virtual_devices(tmp_path):
    """The --four-chips phase on four virtual CPU devices: the sharded lanes
    equal the one-device vmap lanes.  (The CPU reports no device memory, so
    the per-device byte check is stubbed here.)"""
    code = textwrap.dedent(f"""
        import importlib.util, sys
        from pathlib import Path
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", r"{REPO / 'chip_smoke.py'}")
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        from repro.core.axes import LatticeConfig
        cs.peak_bytes = lambda devices: [1] * len(devices)
        cs.four_chip_phase(cs.burst_specs(1), LatticeConfig(),
                           Path(r"{tmp_path}"))
        print("{{}}")
    """)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": str(REPO / "src")}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600, cwd=REPO)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "lane points identical" in r.stdout
    assert json.loads(r.stdout.strip().splitlines()[-1]) == {}
