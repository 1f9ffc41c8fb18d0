"""Unit tests for the HLO cost walker — the backbone of §Roofline.

Compiles small SPMD programs on 8 fake devices (subprocess — device count is
per-process) and checks the walker's FLOPs / collective-bytes / trip-count
accounting against hand-computed values."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.roofline.hlo_parse import (Shape, analyze_hlo_text, parse_hlo,
                                      parse_shapes)

REPO = Path(__file__).resolve().parent.parent


def run_scenario(code: str, timeout=600) -> dict:
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": str(REPO / "src"), "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=timeout, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


class TestShapeParsing:
    def test_scalar_and_tuple(self):
        assert parse_shapes("f32[]")[0].dims == ()
        shs = parse_shapes("(s32[], f32[64,64], bf16[2,3])")
        assert [s.dtype for s in shs] == ["s32", "f32", "bf16"]
        assert shs[2].bytes == 12

    def test_bytes(self):
        assert Shape("bf16", (128, 256)).bytes == 128 * 256 * 2
        assert Shape("pred", (8,)).bytes == 8


@pytest.mark.slow
class TestWalkerOnCompiledHLO:
    def test_scan_trip_counts_and_dot_flops(self):
        out = run_scenario("""
            import json, jax, jax.numpy as jnp
            from jax import lax
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.roofline.hlo_parse import analyze_hlo_text
            mk = {"axis_types": (jax.sharding.AxisType.Auto,) * 2}
            mesh = jax.make_mesh((2, 4), ("data", "model"), **mk)
            def body(x, w):
                return jnp.tanh(x @ w), 0
            def f(x, ws):
                y, _ = lax.scan(body, x, ws)
                return y.sum()
            xs = jax.ShapeDtypeStruct((128, 256), jnp.float32)
            ws = jax.ShapeDtypeStruct((6, 256, 256), jnp.float32)
            with mesh:
                comp = jax.jit(f, in_shardings=(
                    NamedSharding(mesh, P("data", None)),
                    NamedSharding(mesh, P(None, None, "model")))).lower(xs, ws).compile()
            res = analyze_hlo_text(comp.as_text(), 8, bf16_normalize=False)
            print(json.dumps({
                "flops": res["flops_per_device"],
                "trips": list(res["while_trip_counts"].values()),
                "ag": res["coll_counts"].get("all-gather", {}).get("bytes", 0)}))
        """)
        # 6 scan steps x 2*64*64*256 per-device dot flops (+ small elementwise)
        expect_dot = 6 * 2 * 64 * 64 * 256
        assert expect_dot <= out["flops"] <= expect_dot * 1.01
        assert 6 in out["trips"]
        # all-gather of the x shard over 'model' (g=4): 6 x 64x256x4B x 3/4
        assert out["ag"] == pytest.approx(6 * 64 * 256 * 4 * 0.75)

    def test_allreduce_ring_accounting(self):
        out = run_scenario("""
            import json, jax, jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.roofline.hlo_parse import analyze_hlo_text
            mk = {"axis_types": (jax.sharding.AxisType.Auto,)}
            mesh = jax.make_mesh((8,), ("data",), **mk)
            def f(x):
                return x.sum(axis=0)   # cross-device reduction
            xs = jax.ShapeDtypeStruct((8, 1024), jnp.float32)
            with mesh:
                comp = jax.jit(f, in_shardings=(NamedSharding(mesh, P("data", None)),),
                               out_shardings=NamedSharding(mesh, P(None))) \
                    .lower(xs).compile()
            res = analyze_hlo_text(comp.as_text(), 8, bf16_normalize=False)
            ar = res["coll_counts"].get("all-reduce", {"bytes": 0})
            print(json.dumps({"ar_bytes": ar["bytes"]}))
        """)
        # all-reduce of f32[1024] over 8 devices: 2 * 4096B * 7/8
        assert out["ar_bytes"] == pytest.approx(2 * 4096 * 7 / 8, rel=0.01)


class TestWalkerSynthetic:
    HLO = textwrap.dedent("""\
        HloModule test

        %body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
          %p = (s32[], f32[8,8]) parameter(0)
          %i = s32[] get-tuple-element(%p), index=0
          %x = f32[8,8] get-tuple-element(%p), index=1
          %one = s32[] constant(1)
          %i2 = s32[] add(%i, %one)
          %y = f32[8,8] dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
          ROOT %t = (s32[], f32[8,8]) tuple(%i2, %y)
        }

        %cond (p2: (s32[], f32[8,8])) -> pred[] {
          %p2 = (s32[], f32[8,8]) parameter(0)
          %i3 = s32[] get-tuple-element(%p2), index=0
          %lim = s32[] constant(5)
          ROOT %lt = pred[] compare(%i3, %lim), direction=LT
        }

        ENTRY %main (a: f32[8,8]) -> f32[8,8] {
          %a = f32[8,8] parameter(0)
          %zero = s32[] constant(0)
          %init = (s32[], f32[8,8]) tuple(%zero, %a)
          %w = (s32[], f32[8,8]) while(%init), condition=%cond, body=%body
          ROOT %out = f32[8,8] get-tuple-element(%w), index=1
        }
    """)

    def test_trip_count_from_condition_constant(self):
        res = analyze_hlo_text(self.HLO, 1, bf16_normalize=False)
        assert res["while_trip_counts"] == {"w": 5}
        # 5 iterations x 2*8*8*8 dot flops
        assert res["flops_per_device"] == pytest.approx(5 * 2 * 8 * 8 * 8,
                                                        rel=0.05)

    def test_parse_structure(self):
        comps = parse_hlo(self.HLO)
        assert set(comps) == {"body", "cond", "main"}
        assert comps["body"].instrs[-1].opcode == "tuple"
