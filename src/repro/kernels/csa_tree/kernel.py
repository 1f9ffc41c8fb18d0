"""Pallas kernel executing the paper's Fig. 4 carry-save reduction on the VPU.

This is the *bit-exact executable model* of the synthesized adder tree: the
4-2 compressor is evaluated as the "5-3 carry-save adder" of [11] using pure
bitwise ops (XOR/AND/OR + shift), level by level, with a final two-operand add
standing in for the ripple-carry stage.  The reduction schedule is generated
at trace time from the row count, exactly like the netlist builder in
``repro.core.csa`` — so the TPU kernel and the synthesized netlist share
structure.

Layout: operands (H, N) int32 arrive as (H, bn) VMEM blocks (full row dim in
VMEM — the adder tree is a column-local reduction); the grid tiles N.  Rows
and sums stay 2-D (1, bn) tiles: a 1-D int32 block does not match the tiling
XLA gives a 1-D array on the TPU, so the compiled kernel would be refused.  The
whole-rows kernel guards its H <= ``CSA_MAX_ROWS`` VMEM residency assumption
with an explicit ValueError; taller operand stacks go through
``csa_tree_tiled_pallas``, which tiles H into (bh, bn) blocks along a
sequential grid axis and accumulates tile sums in a VMEM scratch — int32
addition wraps mod 2^32 either way, so the tiled result is bit-identical to
the whole-rows kernel and to the ``sum(axis=0)`` oracle for any H.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Row budget of the whole-rows kernel: H int32 rows of a bn-lane block must
#: sit in VMEM at once, and the trace-time reduction schedule unrolls over
#: them — 512 is the seed kernel's stated construction limit, now enforced.
CSA_MAX_ROWS = 512


def _fa(a, b, c):
    """Full adder on integer lanes: exact carry-save split."""
    s = a ^ b ^ c
    carry = ((a & b) | (b & c) | (a & c)) << 1
    return s, carry


def _c42(a, b, c, d, cin):
    """4-2 compressor as a 5-3 carry-save adder (two chained FAs)."""
    s1, cout = _fa(a, b, c)
    s, carry = _fa(s1, d, cin)
    return s, carry, cout


def _reduce_level(lanes: list, rho_comp: bool) -> list:
    """One tree level: compress groups of 4 (compressors) or 3 (FAs)."""
    nxt = []
    i = 0
    if rho_comp:
        cout = None
        while len(lanes) - i >= 4:
            cin = cout if cout is not None else jnp.zeros_like(lanes[0])
            s, c, cout = _c42(lanes[i], lanes[i + 1], lanes[i + 2],
                              lanes[i + 3], cin)
            nxt += [s, c]
            i += 4
        if cout is not None:
            nxt.append(cout)
    while len(lanes) - i >= 3:
        s, c = _fa(lanes[i], lanes[i + 1], lanes[i + 2])
        nxt += [s, c]
        i += 3
    nxt += lanes[i:]
    return nxt


def _reduce_lanes(lanes: list, use_compressors: bool):
    """Run the full reduction schedule down to one lane (tree + final RCA)."""
    guard = 0
    while len(lanes) > 2 and guard < 64:
        guard += 1
        reduced = _reduce_level(lanes, use_compressors)
        if len(reduced) >= len(lanes):            # force progress on tiny n
            a = reduced[0] + reduced[1]
            reduced = [a] + reduced[2:]
        lanes = reduced
    total = lanes[0]
    for l in lanes[1:]:
        total = total + l                          # final RCA
    return total


def _csa_kernel(x_ref, o_ref, *, h: int, use_compressors: bool):
    o_ref[...] = _reduce_lanes([x_ref[i:i + 1, :] for i in range(h)],
                               use_compressors)


@functools.partial(jax.jit, static_argnames=("use_compressors", "bn",
                                             "interpret"))
def csa_tree_pallas(operands: jnp.ndarray, *, use_compressors: bool = True,
                    bn: int = 256, interpret: bool = False) -> jnp.ndarray:
    """Carry-save column reduction: (H, N) int32 -> (N,) int32.

    Whole-rows layout — requires H <= ``CSA_MAX_ROWS``; taller stacks must
    go through :func:`csa_tree_tiled_pallas` (``repro.kernels.csa_tree.
    csa_tree_sum`` routes there automatically)."""
    h, n = operands.shape
    if h > CSA_MAX_ROWS:
        raise ValueError(
            f"csa_tree_pallas keeps all H rows of a block in VMEM and "
            f"unrolls the reduction schedule over them; H={h} exceeds the "
            f"H<={CSA_MAX_ROWS} construction limit — use "
            f"csa_tree_tiled_pallas (csa_tree_sum routes automatically)")
    rem = (-n) % bn
    x = jnp.pad(operands.astype(jnp.int32), ((0, 0), (0, rem)))
    np_ = x.shape[1]
    out = pl.pallas_call(
        functools.partial(_csa_kernel, h=h, use_compressors=use_compressors),
        grid=(np_ // bn,),
        in_specs=[pl.BlockSpec((h, bn), lambda j: (0, j))],
        out_specs=pl.BlockSpec((1, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, np_), jnp.int32),
        interpret=interpret,
    )(x)
    return out[0, :n]


# ---------------------------------------------------------------------------
# Tiled-H variant: unbounded row count via sequential (bh, bn) tile waves
# ---------------------------------------------------------------------------


def _csa_tiled_kernel(x_ref, o_ref, acc_ref, *, bh: int, h_steps: int,
                      use_compressors: bool, mode: str):
    """One (bh, bn) tile per grid step, H innermost (sequential): reduce the
    tile's rows through the CSA schedule, accumulate tile sums in VMEM.
    int32 addition is associative mod 2^32, so the tiling is exact.

    ``mode == "copy"`` (profiling skeleton) keeps the BlockSpec streaming but
    skips the reduction tree — one row read per tile keeps the data
    dependency alive.  There is no compute-only mode: BlockSpec pipelines
    cannot disable their operand streaming, so the profiler derives the
    compute share as fused minus copy."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if mode == "copy":
        acc_ref[...] += x_ref[0:1, :]
    else:
        acc_ref[...] += _reduce_lanes([x_ref[i:i + 1, :] for i in range(bh)],
                                      use_compressors)

    @pl.when(t == h_steps - 1)
    def _out():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("use_compressors", "bh", "bn",
                                             "interpret", "_mode"))
def csa_tree_tiled_pallas(operands: jnp.ndarray, *,
                          use_compressors: bool = True, bh: int = 128,
                          bn: int = 256, interpret: bool = False,
                          _mode: str = "fused") -> jnp.ndarray:
    """Tiled-H carry-save column reduction: (H, N) int32 -> (N,) int32 for
    ANY H.  H pads up to a bh multiple with zero rows (exact: zero lanes
    compress away), N to a bn multiple; the grid walks N tiles x H tiles
    with H sequential so the partial-sum scratch carries across tile waves."""
    h, n = operands.shape
    x = operands.astype(jnp.int32)
    x = jnp.pad(x, ((0, (-h) % bh), (0, (-n) % bn)))
    hp, np_ = x.shape
    h_steps = hp // bh
    out = pl.pallas_call(
        functools.partial(_csa_tiled_kernel, bh=bh, h_steps=h_steps,
                          use_compressors=use_compressors, mode=_mode),
        grid=(np_ // bn, h_steps),
        in_specs=[pl.BlockSpec((bh, bn), lambda j, t: (t, j))],
        out_specs=pl.BlockSpec((1, bn), lambda j, t: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, np_), jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, bn), jnp.int32)],
        interpret=interpret,
    )(x)
    return out[0, :n]
