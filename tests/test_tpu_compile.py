"""Compiles for a described TPU v5e (2x2) topology: no chip is attached, the
TPU compiler refuses here what the chip would refuse.

Covers the service path's device programs at the sizes the chip runs them:
the float64 lattice roll-up kernel at the full lattice, the sharded Pareto
passes on a 4-device mesh, and the three Pallas kernels at serving widths
(plus the smallest int8 ``dcim_mac`` row block the tile tuner can emit).

The topology is described inside a module fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
this file.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core import engine as E
from repro.core import pareto
from repro.core import subcircuits as sc
from repro.core.axes import LatticeConfig
from repro.core.batched import DesignLattice, SpecTables
from repro.core.macro import calibrated_tech_for_reference
from repro.core.multispec import scenario_specs
from repro.kernels.csa_tree import csa_tree_pallas, csa_tree_tiled_pallas
from repro.kernels.dcim_mac import (dcim_matmul_int_pallas,
                                    dcim_matmul_int_pipelined_pallas,
                                    dcim_matmul_pallas,
                                    dcim_matmul_pipelined_pallas)
from repro.kernels.ssm_scan import ssm_scan_pallas, ssm_scan_pipelined_pallas
from repro.kernels.tiles import tile_space
from repro.runtime import enable_x64

#: The qwen3-4b MLP at ``gemm_inventory``'s m=256: up and down projections.
DCIM_SHAPES = [(256, 2560, 9728), (256, 9728, 2560)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A described-chip compile can be written to a persistent cache but
        # never read back without the chip: keep the cache out of it.
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        yield desc
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices), ("spec",))


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _is_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# The float64 lattice roll-up and the sharded Pareto passes
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def packed_group():
    """A 4-lane group of the full 155,520-point lattice, packed for the
    engine kernel."""
    config = LatticeConfig(precision_modes=3, approx_cells=sc.APPROX_CELLS)
    tech = calibrated_tech_for_reference()
    specs = list(scenario_specs().values())
    vision = specs[0]
    specs = [vision] + [s for s in specs[1:]
                        if s.h == vision.h and s.w == vision.w][:3]
    lattices = [DesignLattice.enumerate(s, config=config) for s in specs]
    tables = [SpecTables(s, tech, config=config) for s in specs]
    packed = E.pack_group(lattices, tables)
    assert len(packed) == 4 and len(lattices[0]) == 155_520
    return packed


@pytest.mark.parametrize("placement", ["one-chip", "sharded"])
def test_engine_kernel_full_lattice(packed_group, one_chip, mesh4,
                                    placement):
    tabs_s, consts_s, e_ofu_s, e_align_s = packed_group.operands
    if placement == "one-chip":
        lanes = shared = one_chip
    else:
        lanes = NamedSharding(mesh4, PartitionSpec("spec"))
        shared = NamedSharding(mesh4, PartitionSpec())
    with enable_x64():
        args = (tuple(_spec(a.shape, a.dtype, shared)
                      for a in packed_group.idx),
                tuple(_spec(t.shape, t.dtype, lanes) for t in tabs_s),
                _spec(consts_s.shape, consts_s.dtype, lanes),
                _spec(e_ofu_s.shape, e_ofu_s.dtype, lanes),
                _spec(e_align_s.shape, e_align_s.dtype, lanes))
        compiled = E._eval_kernel_many.lower(*args).compile()
    out = compiled.output_shardings
    assert out["area"].shard_shape((4, 155_520)) == (
        (4, 155_520) if placement == "one-chip" else (1, 155_520))


def test_sharded_pareto_passes(mesh4):
    n, k = 155_520, 3
    n_dev = 4
    m = -(-n // n_dev)
    c = max(64, min(1024, m))
    cr = max(64, pareto.pareto_chunk_size(
        n, k, pareto.DEFAULT_PARETO_BUDGET_BYTES // n_dev))
    fns = pareto._sharded_fns()
    rows = NamedSharding(mesh4, PartitionSpec("spec"))
    replicated = NamedSharding(mesh4, PartitionSpec())
    with enable_x64():
        eps = _spec((), jnp.float64, replicated)
        blk = _spec((n_dev, c, k), jnp.float64, rows)
        fns["local"].lower(blk, blk, eps).compile()
        fns["refine"].lower(_spec((n, k), jnp.float64, replicated),
                            _spec((n_dev, cr, k), jnp.float64, rows),
                            eps).compile()


# ---------------------------------------------------------------------------
# Pallas kernels at serving widths
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", DCIM_SHAPES)
@pytest.mark.parametrize("kernel", [dcim_matmul_int_pipelined_pallas,
                                    dcim_matmul_int_pallas])
def test_dcim_mac_int(one_chip, kernel, shape):
    m, k, n = shape
    compiled = kernel.lower(_spec((m, k), jnp.int8, one_chip),
                            _spec((k, n), jnp.int8, one_chip)).compile()
    assert _is_kernel(compiled)


@pytest.mark.parametrize("shape", DCIM_SHAPES)
@pytest.mark.parametrize("kernel", [dcim_matmul_pipelined_pallas,
                                    dcim_matmul_pallas])
def test_dcim_mac_dequant(one_chip, kernel, shape):
    m, k, n = shape
    compiled = kernel.lower(
        _spec((m, k), jnp.int8, one_chip), _spec((k, n), jnp.int8, one_chip),
        _spec((m,), jnp.float32, one_chip),
        _spec((n,), jnp.float32, one_chip)).compile()
    assert _is_kernel(compiled)


@pytest.mark.parametrize("kernel", [dcim_matmul_int_pipelined_pallas,
                                    dcim_matmul_int_pallas])
def test_dcim_mac_smallest_int8_row_block(one_chip, kernel):
    """The tuner's smallest int8 row block (a decode-sized M) compiles."""
    _, k, n = DCIM_SHAPES[0]
    bm = min(tc.bm for tc in tile_space("dcim_mac", (1, k, n)))
    compiled = kernel.lower(_spec((bm, k), jnp.int8, one_chip),
                            _spec((k, n), jnp.int8, one_chip),
                            bm=bm).compile()
    assert _is_kernel(compiled)


@pytest.mark.parametrize("kernel", [ssm_scan_pipelined_pallas,
                                    ssm_scan_pallas])
def test_ssm_scan(one_chip, kernel):
    t, d = 4096, 4096                     # rwkv6-7b's width
    compiled = kernel.lower(_spec((t, d), jnp.float32, one_chip),
                            _spec((t, d), jnp.float32, one_chip),
                            _spec((d,), jnp.float32, one_chip)).compile()
    assert _is_kernel(compiled)


@pytest.mark.parametrize("kernel,shape", [(csa_tree_tiled_pallas, (1024, 512)),
                                          (csa_tree_pallas, (512, 512))])
def test_csa_tree(one_chip, kernel, shape):
    compiled = kernel.lower(_spec(shape, jnp.int32, one_chip)).compile()
    assert _is_kernel(compiled)
