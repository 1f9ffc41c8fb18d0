"""Pareto frontier of the plain reference: a copy of the program's
eps-band dominance (``repro.core.pareto``), kept here so the yardstick does
not move with the program.  Only the host path is kept: the exact
block-local prefilter, the refinement against every row, and the
dedup/order pass that defines frontier membership and order.
"""

from __future__ import annotations

from typing import Callable, Iterable, Sequence, TypeVar

import numpy as np

T = TypeVar("T")

#: Shared tie/epsilon band for dominance comparisons and duplicate collapse.
#: Every frontier in the repo — the scalar :func:`dominates` /
#: :func:`pareto_indices` path, the batched engine's chunked
#: ``pareto_mask``, and the multi-spec extraction — compares through this one
#: constant, so near-tie objectives land on the *same* frontier no matter
#: which path evaluated them.  The band is absolute: an objective whose scale
#: approaches it (e.g. period in seconds, ~1e-9) effectively gets a relative
#: tolerance.
PARETO_EPS = 1e-12


def dominates(a: Sequence[float], b: Sequence[float],
              eps: float = PARETO_EPS) -> bool:
    """True if objective vector ``a`` Pareto-dominates ``b`` (all <=, one <,
    with the shared ``eps`` tie band).  Objectives are minimized."""
    le = all(x <= y + eps for x, y in zip(a, b))
    lt = any(x < y - eps for x, y in zip(a, b))
    return le and lt


def chunk_dominated(all_o, blk, eps, xp=np):
    """Eps-band dominance verdicts for one chunk: entry ``i`` is True iff
    some row of ``all_o`` dominates ``blk[i]`` under exactly the
    :func:`dominates` semantics.  This is the *single* implementation of the
    vectorized predicate — :func:`nondominated_mask` runs it on numpy, the
    batched engine's ``pareto_mask`` passes ``xp=jax.numpy`` to run the same
    comparisons on device, and :func:`nondominated_mask_sharded` vmaps it
    across device shards."""
    c, k = blk.shape
    n = all_o.shape[0]
    le = xp.ones((c, n), dtype=bool)
    lt = xp.zeros((c, n), dtype=bool)
    for d in range(k):
        le = le & (all_o[None, :, d] <= blk[:, None, d] + eps)
        lt = lt | (all_o[None, :, d] < blk[:, None, d] - eps)
    return (le & lt).any(axis=1)


def _as_matrix(objs) -> np.ndarray:
    objs = np.asarray(objs, dtype=np.float64)
    if objs.ndim == 1:
        objs = objs[:, None]
    return objs


#: Rows per block of the host mask's first (block-local) phase: small blocks
#: keep its quadratic cost low while still eliminating most dominated rows.
LOCAL_BLOCK = 128


def nondominated_mask(objs, eps: float = PARETO_EPS,
                      chunk: int = 1024) -> np.ndarray:
    """Boolean non-dominated mask over an (n, k) objective matrix
    (minimization), vectorized and chunked.  Entry ``i`` is True iff no row
    dominates row ``i`` under exactly the :func:`dominates` semantics — this
    is the single dominance predicate :func:`pareto_indices` and the batched
    engine's ``pareto_mask`` both reduce to.

    Runs as an exact pass in three phases: phase 1 tests each
    :data:`LOCAL_BLOCK`-row block only against itself (a point dominated inside its own block is
    dominated, full stop — the witness is a real row), phase 2 tests the
    local survivors against each other (again a real witness), and phase 3
    refines what is left against *all* rows.  Because eps-band dominance is
    not transitive, the refinement deliberately compares against every row,
    not just other survivors; the resulting mask is identical to the naive
    all-pairs pass at a fraction of the cost (frontiers are small, so few
    points reach phase 3)."""
    objs = _as_matrix(objs)
    n = objs.shape[0]
    keep = np.ones(n, dtype=bool)
    if n == 0:
        return keep
    for start in range(0, n, LOCAL_BLOCK):
        blk = objs[start:start + LOCAL_BLOCK]           # (c, k)
        keep[start:start + blk.shape[0]] = ~chunk_dominated(blk, blk, eps)
    survivors = np.flatnonzero(keep)
    peers = objs[survivors]
    for start in range(0, survivors.size, chunk):
        idx = survivors[start:start + chunk]
        keep[idx] = ~chunk_dominated(peers, objs[idx], eps)
    survivors = np.flatnonzero(keep)
    for start in range(0, survivors.size, chunk):
        idx = survivors[start:start + chunk]
        keep[idx] = ~chunk_dominated(objs, objs[idx], eps)
    return keep


def pareto_indices(objs: Sequence[Sequence[float]],
                   mask_fn: Callable[[np.ndarray], np.ndarray] | None = None
                   ) -> list[int]:
    """Indices of the non-dominated, deduplicated members of ``objs``, sorted
    by objective tuple.  This is the single source of truth for frontier
    semantics: :func:`pareto_front` and the batched engine's vectorized
    extraction both reduce to it, so scalar and batched sweeps agree exactly.

    Dominance testing delegates to the vectorized :func:`nondominated_mask`
    (the per-pair Python walk was O(N^2) and hung at lattice scale); callers
    at lattice scale may pass ``mask_fn=nondominated_mask_auto`` (or the
    sharded mask directly) — every mask implementation returns the same bits.
    The documented output order is preserved exactly: near-duplicates (all
    coordinates within :data:`PARETO_EPS`) keep their first occurrence in
    input order, and the surviving set is sorted by objective tuple."""
    objs = list(objs)
    if not objs:
        return []
    arr = np.asarray([[float(x) for x in o] for o in objs], dtype=np.float64)
    survivors = np.flatnonzero((mask_fn or nondominated_mask)(arr))
    # Dedup in input order against the accepted set (vectorized per survivor,
    # matching the incremental semantics of the original Python walk).
    acc = np.empty((survivors.size, arr.shape[1]), dtype=np.float64)
    n_acc = 0
    front: list[tuple[Sequence[float], int]] = []
    for i in survivors:
        o = arr[i]
        if n_acc and (np.abs(acc[:n_acc] - o) < PARETO_EPS).all(axis=1).any():
            continue
        acc[n_acc] = o
        n_acc += 1
        front.append((objs[i], int(i)))
    front.sort(key=lambda oi: tuple(oi[0]))
    return [i for _, i in front]


def pareto_front(items: Iterable[T], objectives: Callable[[T], Sequence[float]]
                 ) -> list[T]:
    """Filter ``items`` to the non-dominated set, stably ordered by the first
    objective."""
    items = list(items)
    objs = [objectives(it) for it in items]
    return [items[i] for i in pareto_indices(objs)]


def preference_grid(resolution: int = 4) -> list[tuple[float, float, float]]:
    """Deterministic simplex grid over (power, area, throughput) preference
    weights — the multi-spec sweep driving the searcher.

    ``resolution`` must be >= 1: a 0-resolution grid would be empty and every
    sweep built on it would silently synthesize nothing."""
    if resolution < 1:
        raise ValueError(
            f"preference_grid needs resolution >= 1, got {resolution}: an "
            "empty grid silently yields empty sweeps downstream")
    out = []
    for a in range(resolution + 1):
        for b in range(resolution + 1 - a):
            c = resolution - a - b
            if a == b == c == 0:
                continue
            out.append((a / resolution, b / resolution, c / resolution))
    return out
