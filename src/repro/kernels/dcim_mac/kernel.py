"""Pallas TPU kernel for the DCIM MAC (the paper's compute hot-spot, adapted
to the MXU — see DESIGN.md §4 "Hardware adaptation").

The circuit mechanism (bit-serial WL streaming + per-column adder trees) has
no MXU analogue; the TPU-native mapping keeps the *roles*:

  weight-stationary SRAM tile   ->  W block resident in VMEM (BlockSpec)
  bitwise multiplier + CSA tree ->  MXU systolic int multiply-accumulate
  S&A (full-width accumulation) ->  int32 accumulator scratch in VMEM
  OFU / alignment epilogue      ->  fused per-channel dequant on final k step

Blocked matmul with grid (M/bm, N/bn, K/bk), k innermost (sequential on TPU)
so the int32 accumulator lives in a VMEM scratch across k steps.  The
dequant scales ride as (M, 1) and (1, N) columns: a 1-D f32 block does not
match the tiling XLA gives a 1-D array on the TPU.  Block shapes
default to MXU-aligned 128 multiples; int8 operands, int32 accumulate
(``preferred_element_type``), bf16/f32 output after the epilogue.

Two execution styles share the wrappers:

  * the classic BlockSpec grid kernel (``depth == 1``): operand staging is
    left to the pipeline the Mosaic compiler builds for the declared blocks;
  * the **multi-buffered manual pipeline** (``depth >= 2``,
    ``dcim_matmul_pipelined_pallas``): A/W live in HBM (``memory_space=ANY``)
    and the kernel streams (bm, bk)/(bk, bn) chunks itself through
    ``pltpu.make_async_copy`` into a ``depth``-slot VMEM scratch rotation, so
    the fetch of K-chunk t+1..t+depth-1 overlaps the MXU pass on chunk t.
    Buffer depth is a tunable the autotuner sweeps.

Both compute identical int32 bits (the adder tree is exact either way);
``_mode`` exposes copy-only / compute-only skeletons of the same pipeline to
the DMA-vs-compute profiling harness (:mod:`repro.kernels.profile`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mac_kernel(a_ref, w_ref, asc_ref, wsc_ref, o_ref, acc_ref, *, k_steps: int,
                out_dtype):
    """One (bm, bn) output tile; accumulates over the k grid axis."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # MXU: int8 x int8 -> int32 (the adder tree + S&A, full width, one shot).
    acc_ref[...] += jnp.dot(a_ref[...], w_ref[...],
                            preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        # OFU-equivalent: per-row activation scale x per-column weight scale.
        scale = asc_ref[...] * wsc_ref[...]
        o_ref[...] = (acc_ref[...].astype(jnp.float32) * scale).astype(out_dtype)


def _int_kernel(a_ref, w_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], w_ref[...],
                            preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _out():
        o_ref[...] = acc_ref[...]


def _pad_to(x: jnp.ndarray, mults: tuple[int, ...]) -> jnp.ndarray:
    pads = []
    for dim, m in zip(x.shape, mults):
        rem = (-dim) % m
        pads.append((0, rem))
    if any(p[1] for p in pads):
        x = jnp.pad(x, pads)
    return x


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype",
                                             "interpret"))
def dcim_matmul_pallas(a_q: jnp.ndarray, w_q: jnp.ndarray,
                       a_scale: jnp.ndarray, w_scale: jnp.ndarray,
                       *, bm: int = 128, bn: int = 128, bk: int = 128,
                       out_dtype=jnp.float32,
                       interpret: bool = False) -> jnp.ndarray:
    """Quantized matmul with fused dequant: (M,K)i8 @ (K,N)i8 -> (M,N).

    ``a_scale``: per-row (M,) f32; ``w_scale``: per-column (N,) f32.
    Shapes are padded up to block multiples and the result sliced back.
    """
    m, k = a_q.shape
    k2, n = w_q.shape
    assert k == k2, (a_q.shape, w_q.shape)
    a_scale = jnp.broadcast_to(jnp.asarray(a_scale, jnp.float32), (m,))
    w_scale = jnp.broadcast_to(jnp.asarray(w_scale, jnp.float32), (n,))

    a_p = _pad_to(a_q, (bm, bk))
    w_p = _pad_to(w_q, (bk, bn))
    asc = _pad_to(a_scale[:, None], (bm, 1))
    wsc = _pad_to(w_scale[None, :], (1, bn))
    mp, kp = a_p.shape
    _, np_ = w_p.shape
    grid = (mp // bm, np_ // bn, kp // bk)

    out = pl.pallas_call(
        functools.partial(_mac_kernel, k_steps=grid[2], out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, t: (i, t)),
            pl.BlockSpec((bk, bn), lambda i, j, t: (t, j)),
            pl.BlockSpec((bm, 1), lambda i, j, t: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, t: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(a_p, w_p, asc, wsc)
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def dcim_matmul_int_pallas(a_q: jnp.ndarray, w_q: jnp.ndarray,
                           *, bm: int = 128, bn: int = 128, bk: int = 128,
                           interpret: bool = False) -> jnp.ndarray:
    """Integer-out variant (no epilogue): (M,K)i8 @ (K,N)i8 -> (M,N)i32."""
    m, k = a_q.shape
    _, n = w_q.shape
    a_p = _pad_to(a_q, (bm, bk))
    w_p = _pad_to(w_q, (bk, bn))
    mp, kp = a_p.shape
    _, np_ = w_p.shape
    grid = (mp // bm, np_ // bn, kp // bk)
    out = pl.pallas_call(
        functools.partial(_int_kernel, k_steps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, t: (i, t)),
            pl.BlockSpec((bk, bn), lambda i, j, t: (t, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(a_p, w_p)
    return out[:m, :n]


# ---------------------------------------------------------------------------
# Multi-buffered manual DMA pipeline (depth-slot VMEM rotation over K chunks)
# ---------------------------------------------------------------------------


def _pipelined_mac_body(a_hbm, w_hbm, a_buf, w_buf, sems, *, bm: int, bn: int,
                        bk: int, k_steps: int, depth: int, mode: str):
    """Stream K chunks of one (bm, bn) output tile through a ``depth``-slot
    buffer rotation and accumulate the int32 partial sums.

    ``mode``: "fused" (real kernel), "copy" (DMA only, no MXU — the
    bandwidth leg of the profiling harness), "compute" (MXU only on resident
    buffers, no DMA — the compute leg)."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    stream = mode != "compute"

    def a_dma(slot, t):
        return pltpu.make_async_copy(
            a_hbm.at[pl.ds(i * bm, bm), pl.ds(t * bk, bk)],
            a_buf.at[slot], sems.at[0, slot])

    def w_dma(slot, t):
        return pltpu.make_async_copy(
            w_hbm.at[pl.ds(t * bk, bk), pl.ds(j * bn, bn)],
            w_buf.at[slot], sems.at[1, slot])

    if stream:
        for t in range(min(depth, k_steps)):          # pipeline warm-up
            a_dma(t, t).start()
            w_dma(t, t).start()

    def body(t, acc):
        slot = jax.lax.rem(t, depth)
        if stream:
            a_dma(slot, t).wait()
            w_dma(slot, t).wait()
        if mode != "copy":
            src = slot if stream else 0
            acc = acc + jnp.dot(a_buf[src], w_buf[src],
                                preferred_element_type=jnp.int32)
        if stream:
            # chunk t is consumed; its slot refetches chunk t + depth
            @pl.when(t + depth < k_steps)
            def _():
                a_dma(slot, t + depth).start()
                w_dma(slot, t + depth).start()
        return acc

    acc = jax.lax.fori_loop(0, k_steps, body,
                            jnp.zeros((bm, bn), jnp.int32))
    if mode == "copy":
        # Data-depend the output on the streamed bytes so the DMA chain
        # survives DCE even though no math consumed it.
        acc = acc + (a_buf[0, 0, 0].astype(jnp.int32)
                     + w_buf[0, 0, 0].astype(jnp.int32))
    return acc


def _mac_pipelined_kernel(a_hbm, w_hbm, asc_ref, wsc_ref, o_ref, a_buf,
                          w_buf, sems, *, bm: int, bn: int, bk: int,
                          k_steps: int, depth: int, out_dtype, mode: str):
    acc = _pipelined_mac_body(a_hbm, w_hbm, a_buf, w_buf, sems, bm=bm, bn=bn,
                              bk=bk, k_steps=k_steps, depth=depth, mode=mode)
    scale = asc_ref[...] * wsc_ref[...]
    o_ref[...] = (acc.astype(jnp.float32) * scale).astype(out_dtype)


def _int_pipelined_kernel(a_hbm, w_hbm, o_ref, a_buf, w_buf, sems, *,
                          bm: int, bn: int, bk: int, k_steps: int,
                          depth: int, mode: str):
    o_ref[...] = _pipelined_mac_body(a_hbm, w_hbm, a_buf, w_buf, sems,
                                     bm=bm, bn=bn, bk=bk, k_steps=k_steps,
                                     depth=depth, mode=mode)


def _pipeline_scratch(bm: int, bn: int, bk: int, depth: int):
    return [pltpu.VMEM((depth, bm, bk), jnp.int8),
            pltpu.VMEM((depth, bk, bn), jnp.int8),
            pltpu.SemaphoreType.DMA((2, depth))]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "depth",
                                             "out_dtype", "interpret",
                                             "_mode"))
def dcim_matmul_pipelined_pallas(a_q: jnp.ndarray, w_q: jnp.ndarray,
                                 a_scale: jnp.ndarray, w_scale: jnp.ndarray,
                                 *, bm: int = 128, bn: int = 128,
                                 bk: int = 128, depth: int = 2,
                                 out_dtype=jnp.float32,
                                 interpret: bool = False,
                                 _mode: str = "fused") -> jnp.ndarray:
    """Quantized matmul with fused dequant through the manual multi-buffered
    DMA pipeline.  Bit-identical accumulation to :func:`dcim_matmul_pallas`;
    ``depth`` VMEM slots of (bm, bk) + (bk, bn) operand chunks rotate so
    HBM->VMEM fetch overlaps the MXU."""
    m, k = a_q.shape
    _, n = w_q.shape
    a_scale = jnp.broadcast_to(jnp.asarray(a_scale, jnp.float32), (m,))
    w_scale = jnp.broadcast_to(jnp.asarray(w_scale, jnp.float32), (n,))
    a_p = _pad_to(a_q, (bm, bk))
    w_p = _pad_to(w_q, (bk, bn))
    asc = _pad_to(a_scale[:, None], (bm, 1))
    wsc = _pad_to(w_scale[None, :], (1, bn))
    mp, kp = a_p.shape
    _, np_ = w_p.shape
    grid = (mp // bm, np_ // bn)
    out = pl.pallas_call(
        functools.partial(_mac_pipelined_kernel, bm=bm, bn=bn, bk=bk,
                          k_steps=kp // bk, depth=depth, out_dtype=out_dtype,
                          mode=_mode),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=_pipeline_scratch(bm, bn, bk, depth),
        interpret=interpret,
    )(a_p, w_p, asc, wsc)
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "depth",
                                             "interpret", "_mode"))
def dcim_matmul_int_pipelined_pallas(a_q: jnp.ndarray, w_q: jnp.ndarray,
                                     *, bm: int = 128, bn: int = 128,
                                     bk: int = 128, depth: int = 2,
                                     interpret: bool = False,
                                     _mode: str = "fused") -> jnp.ndarray:
    """Integer-out variant of the multi-buffered pipeline: bit-identical to
    :func:`dcim_matmul_int_pallas` (and hence the bit-serial DCIM oracle)."""
    m, k = a_q.shape
    _, n = w_q.shape
    a_p = _pad_to(a_q, (bm, bk))
    w_p = _pad_to(w_q, (bk, bn))
    mp, kp = a_p.shape
    _, np_ = w_p.shape
    grid = (mp // bm, np_ // bn)
    out = pl.pallas_call(
        functools.partial(_int_pipelined_kernel, bm=bm, bn=bn, bk=bk,
                          k_steps=kp // bk, depth=depth, mode=_mode),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        scratch_shapes=_pipeline_scratch(bm, bn, bk, depth),
        interpret=interpret,
    )(a_p, w_p)
    return out[:m, :n]
