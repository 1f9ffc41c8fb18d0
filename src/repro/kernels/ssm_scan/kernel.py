"""Pallas TPU kernel: chunked diagonal linear recurrence.

h_t = a_t * h_{t-1} + b_t over T steps of width-D state, the primitive under
the SSM (Mamba2 / RWKV6) layers for long-context decode — the ``long_500k``
shape class runs on this.

TPU adaptation: the recurrence is sequential in T, so the kernel tiles T into
chunks along the (sequential) grid axis and carries the running state in a
VMEM scratch between grid steps — a weight-stationary-style pipeline where
HBM->VMEM streaming of (a, b) chunks overlaps the VPU scan of the previous
chunk.  Within a chunk the scan runs as an unrolled log-depth associative
doubling (Blelloch up-sweep) over VREGs rather than a length-bt serial loop:
bt=128 costs 7 vector passes instead of 128.

D tiles along the second grid axis (lanes, 128-aligned); T chunks along the
last (sequential) axis.

``ssm_scan_pipelined_pallas`` is the multi-buffered variant: (a, b) stay in
HBM and the kernel streams (bt, bd) chunks itself through
``pltpu.make_async_copy`` into a ``depth``-slot VMEM rotation, with states
written back through a matching ``depth``-slot output staging rotation — so
chunk t+1..t+depth-1 fetch and chunk t-1 write-back both overlap the VPU
scan of chunk t.  Same arithmetic per chunk, so the tolerance contract vs
``ssm_scan_ref`` is unchanged; ``_mode`` exposes copy-only / compute-only
skeletons to the profiling harness.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _chunk_states(a, b, h_in, *, bt: int):
    """States of one (bt, bd) chunk given the (1, bd) incoming carry: log-depth
    associative doubling — (A, B)_t composes prefix products,
    shift-and-combine doubles span — then states_t = A_t * h_in + B_t."""
    A, B = a, b
    span = 1
    while span < bt:
        A_shift = jnp.concatenate(
            [jnp.ones((span, A.shape[1]), A.dtype), A[:-span]], axis=0)
        B_shift = jnp.concatenate(
            [jnp.zeros((span, B.shape[1]), B.dtype), B[:-span]], axis=0)
        B = A * B_shift + B
        A = A * A_shift
        span *= 2
    return A * h_in + B


def _scan_chunk_kernel(a_ref, b_ref, h0_ref, o_ref, hfin_ref, carry_ref,
                       *, bt: int, t_steps: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        carry_ref[...] = h0_ref[...]

    # (bt, bd) chunk; prefix-inclusive states from the carried state.
    states = _chunk_states(a_ref[...], b_ref[...], carry_ref[...], bt=bt)
    o_ref[...] = states
    carry_ref[...] = states[bt - 1:bt, :]

    @pl.when(t == t_steps - 1)
    def _final():
        hfin_ref[...] = carry_ref[...]


@functools.partial(jax.jit, static_argnames=("bt", "bd", "interpret"))
def ssm_scan_pallas(a: jnp.ndarray, b: jnp.ndarray, h0: jnp.ndarray,
                    *, bt: int = 128, bd: int = 128,
                    interpret: bool = False) -> tuple[jnp.ndarray, jnp.ndarray]:
    """a, b: (T, D) f32; h0: (D,) f32 -> (states (T, D), final (D,))."""
    t_len, d = a.shape
    pt, pd = (-t_len) % bt, (-d) % bd
    # Pad T with identity steps (a=1 keeps the carry; harmless since padded
    # rows are sliced off) — wait: a=1,b=0 *propagates* the carry, and padded
    # states are discarded, so the final state must come from the last REAL
    # row; we pad with a=1, b=0 and read the carry after the last real row by
    # slicing states.
    a_p = jnp.pad(a, ((0, pt), (0, pd)), constant_values=1.0)
    b_p = jnp.pad(b, ((0, pt), (0, pd)))
    h0_p = jnp.pad(h0, (0, pd))
    tp, dp = a_p.shape
    grid = (dp // bd, tp // bt)  # T innermost: sequential carry axis

    states, hfin = pl.pallas_call(
        functools.partial(_scan_chunk_kernel, bt=bt, t_steps=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, bd), lambda j, t: (t, j)),
            pl.BlockSpec((bt, bd), lambda j, t: (t, j)),
            pl.BlockSpec((1, bd), lambda j, t: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bt, bd), lambda j, t: (t, j)),
            pl.BlockSpec((1, bd), lambda j, t: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tp, dp), a.dtype),
            jax.ShapeDtypeStruct((1, dp), a.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((1, bd), a.dtype)],
        interpret=interpret,
    )(a_p, b_p, h0_p[None, :])
    out_states = states[:t_len, :d]
    final = out_states[-1, :] if pt else hfin[0, :d]
    return out_states, final


# ---------------------------------------------------------------------------
# Multi-buffered manual DMA pipeline (depth-slot rotation over T chunks)
# ---------------------------------------------------------------------------


def _scan_pipelined_kernel(a_hbm, b_hbm, h0_ref, o_hbm, hfin_ref, a_buf,
                           b_buf, o_buf, in_sems, out_sems, *, bt: int,
                           t_steps: int, depth: int, mode: str):
    """One D tile's full T sweep: stream (bt, bd) chunks of a and b through
    ``depth`` input slots, scan each chunk, and stream states back out
    through ``depth`` staging slots.  The carry rides the fori_loop."""
    j = pl.program_id(0)
    stream = mode != "compute"

    def in_dma(hbm, buf, row, slot, t):
        return pltpu.make_async_copy(
            hbm.at[pl.ds(t * bt, bt), pl.ds(j * a_buf.shape[2], a_buf.shape[2])],
            buf.at[slot], in_sems.at[row, slot])

    def out_dma(slot, t):
        return pltpu.make_async_copy(
            o_buf.at[slot],
            o_hbm.at[pl.ds(t * bt, bt),
                     pl.ds(j * o_buf.shape[2], o_buf.shape[2])],
            out_sems.at[slot])

    if stream:
        for t in range(min(depth, t_steps)):          # pipeline warm-up
            in_dma(a_hbm, a_buf, 0, t, t).start()
            in_dma(b_hbm, b_buf, 1, t, t).start()

    def body(t, h):
        slot = jax.lax.rem(t, depth)
        if stream:
            in_dma(a_hbm, a_buf, 0, slot, t).wait()
            in_dma(b_hbm, b_buf, 1, slot, t).wait()

            # The write-back that borrowed this staging slot ``depth`` chunks
            # ago must drain before the slot is overwritten.
            @pl.when(t >= depth)
            def _():
                out_dma(slot, t - depth).wait()
        if mode == "copy":
            o_buf[slot] = a_buf[slot] + b_buf[slot]
        else:
            src = slot if stream else 0
            states = _chunk_states(a_buf[src], b_buf[src], h, bt=bt)
            h = states[bt - 1:bt, :]
            if stream:
                o_buf[slot] = states
        if stream:
            out_dma(slot, t).start()

            @pl.when(t + depth < t_steps)
            def _():
                in_dma(a_hbm, a_buf, 0, slot, t + depth).start()
                in_dma(b_hbm, b_buf, 1, slot, t + depth).start()
        return h

    h = jax.lax.fori_loop(0, t_steps, body, h0_ref[...])
    if stream:
        for t in range(max(0, t_steps - depth), t_steps):   # drain stores
            out_dma(t % depth, t).wait()
    hfin_ref[...] = h


@functools.partial(jax.jit, static_argnames=("bt", "bd", "depth",
                                             "interpret", "_mode"))
def ssm_scan_pipelined_pallas(a: jnp.ndarray, b: jnp.ndarray,
                              h0: jnp.ndarray, *, bt: int = 128,
                              bd: int = 128, depth: int = 2,
                              interpret: bool = False, _mode: str = "fused"
                              ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Multi-buffered variant of :func:`ssm_scan_pallas` — same contract,
    same per-chunk arithmetic, explicit HBM<->VMEM streaming with a tunable
    buffer depth."""
    t_len, d = a.shape
    pt, pd = (-t_len) % bt, (-d) % bd
    a_p = jnp.pad(a, ((0, pt), (0, pd)), constant_values=1.0)
    b_p = jnp.pad(b, ((0, pt), (0, pd)))
    h0_p = jnp.pad(h0, (0, pd))
    tp, dp = a_p.shape
    t_steps = tp // bt

    states, hfin = pl.pallas_call(
        functools.partial(_scan_pipelined_kernel, bt=bt, t_steps=t_steps,
                          depth=depth, mode=_mode),
        grid=(dp // bd,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, bd), lambda j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, bd), lambda j: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tp, dp), a.dtype),
            jax.ShapeDtypeStruct((1, dp), a.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((depth, bt, bd), a.dtype),
            pltpu.VMEM((depth, bt, bd), a.dtype),
            pltpu.VMEM((depth, bt, bd), a.dtype),
            pltpu.SemaphoreType.DMA((2, depth)),
            pltpu.SemaphoreType.DMA((depth,)),
        ],
        interpret=interpret,
    )(a_p, b_p, h0_p[None, :])
    out_states = states[:t_len, :d]
    final = out_states[-1, :] if pt else hfin[0, :d]
    return out_states, final
