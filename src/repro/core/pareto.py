"""Pareto-frontier utilities (paper §III-C / Fig. 8).

The MSO searcher emits a *set* of design points; the compiler returns those on
the Pareto frontier of (power, area, latency) under the throughput constraint,
"to be finally chosen based on defined PPA preferences or user selection".

Extraction runs in one of three places, all computing the exact same
eps-band verdicts (bit-identical masks, same output order):

  :func:`nondominated_mask`          host numpy, exact in three steps:
                                     collapse bitwise-equal rows, a sorted
                                     witness prefilter over the distinct
                                     rows, then every prefilter survivor is
                                     refined against *all* distinct rows;
  :func:`repro.core.batched.pareto_mask`
                                     the same chunked predicate on one device;
  :func:`nondominated_mask_sharded`  jitted map-reduce across every visible
                                     device — per-shard local frontier,
                                     gather survivors, cross-shard refinement
                                     (lattice-scale frontiers).

This module stays importable without jax (the scalar compiler layer is
numpy-only); the sharded path imports jax lazily on first use.
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


#: Rows per block of the host mask's sorted witness walk, and the largest
#: input it masks with one all-pairs test instead.  Set by measurement on
#: sweep-sized inputs: smaller blocks pay more numpy calls per row, larger
#: ones a quadratic self-test that witnesses would mostly have made moot.
WALK_BLOCK = 512


def nondominated_mask_counts(objs, eps: float = PARETO_EPS,
                             chunk: int = 1024) -> tuple[np.ndarray, int, int]:
    """:func:`nondominated_mask`, with the two counts that show how far its
    steps cut the work: ``(mask, distinct, survivors)`` — the rows left after
    the exact-duplicate collapse and the rows that reach the refinement.  An
    input of at most :data:`WALK_BLOCK` rows is masked by one all-pairs test,
    so both counts are its row count."""
    objs = _as_matrix(objs)
    n = objs.shape[0]
    if n <= WALK_BLOCK:
        return ~chunk_dominated(objs, objs, eps), n, n
    # 1. Collapse bitwise-equal rows: their verdicts are equal (the predicate
    #    reads only the two tuples) and they never dominate each other.
    objs = np.ascontiguousarray(objs)
    bits = objs.view(np.int64)
    order = np.lexsort(bits.T)
    bits = bits[order]
    head = np.empty(n, dtype=bool)
    head[0] = True
    np.any(bits[1:] != bits[:-1], axis=1, out=head[1:])
    rows = objs[order[head]]
    group = np.empty(n, dtype=np.intp)
    group[order] = np.cumsum(head) - 1
    # 2. Witness prefilter: walk the distinct rows by their min-max
    #    normalised objective sum, so dominating rows tend to come first.
    with np.errstate(invalid="ignore", over="ignore"):
        lo = rows.min(axis=0)
        span = rows.max(axis=0) - lo
        span[~(span > 0)] = 1.0                  # constant column
        walk = np.argsort(((rows - lo) / span).sum(axis=1), kind="stable")
    keep = np.zeros(rows.shape[0], dtype=bool)
    witnesses = rows[:0]
    for start in range(0, walk.size, WALK_BLOCK):
        idx = walk[start:start + WALK_BLOCK]
        blk = rows[idx]
        # Witnesses go in batches of 1, 2, 4, ...: the first few remove most
        # rows, and the block is compacted after each batch.
        lo_w, batch = 0, 1
        while lo_w < len(witnesses) and idx.size:
            live = ~chunk_dominated(witnesses[lo_w:lo_w + batch], blk, eps)
            idx, blk = idx[live], blk[live]
            lo_w, batch = lo_w + batch, 2 * batch
        if idx.size:
            live = ~chunk_dominated(blk, blk, eps)
            keep[idx[live]] = True
            witnesses = np.concatenate([witnesses, blk[live]])
    # 3. Refinement against every distinct row: eps-band dominance is not
    #    transitive, so a survivor's dominator may be a removed row.
    survivors = np.flatnonzero(keep)
    for start in range(0, survivors.size, chunk):
        idx = survivors[start:start + chunk]
        keep[idx] = ~chunk_dominated(rows, rows[idx], eps)
    return keep[group], rows.shape[0], survivors.size


def nondominated_mask(objs, eps: float = PARETO_EPS,
                      chunk: int = 1024) -> np.ndarray:
    """Boolean non-dominated mask over an (n, k) objective matrix
    (minimization).  Entry ``i`` is True iff no row dominates row ``i``
    under exactly the :func:`dominates` semantics — this is the single
    dominance predicate :func:`pareto_indices` and the batched engine's
    ``pareto_mask`` both reduce to.

    An input of at most :data:`WALK_BLOCK` rows is one all-pairs test.  A
    larger one is masked exactly in three steps, every comparison through
    :func:`chunk_dominated`:

      1. *collapse* — rows with bitwise-equal objective tuples get the same
         verdict, so one ``lexsort`` groups them and steps 2 and 3 run on
         the distinct rows only (sweep lattices repeat most tuples);
      2. *sorted witness prefilter* — the distinct rows are walked in
         :data:`WALK_BLOCK`-row blocks in order of their min-max normalised
         objective sum; each block is tested against the witnesses accepted
         so far, then against itself, and its survivors become witnesses.
         Every row removed here has a real dominating row;
      3. *refinement* — each prefilter survivor is tested against *all*
         distinct rows.  Eps-band dominance is not transitive, so this step,
         not the prefilter, is what makes the mask equal to the naive
         all-pairs pass.

    ``chunk`` bounds the survivors refined per call."""
    return nondominated_mask_counts(objs, eps, chunk)[0]


#: Default device-memory budget for one Pareto chunk's comparison masks.
DEFAULT_PARETO_BUDGET_BYTES = 256 * 1024 * 1024


def pareto_chunk_size(n_points: int, n_objectives: int = 3,
                      budget_bytes: int = DEFAULT_PARETO_BUDGET_BYTES) -> int:
    """Chunk size for the chunked Pareto masks such that the peak comparison
    footprint fits the accelerator budget.

    One chunk row holds the ``le``/``lt`` masks plus one comparison temp per
    objective against all ``n_points`` columns (~1 byte each), so a chunk
    costs about ``chunk * n_points * (2 + n_objectives)`` bytes."""
    per_row = max(1, n_points) * (2 + max(1, n_objectives))
    chunk = budget_bytes // per_row
    return int(min(max(chunk, 64), max(n_points, 64)))


# ---------------------------------------------------------------------------
# Device-sharded extraction (lattice-scale frontiers)
# ---------------------------------------------------------------------------

#: Below this point count the host pass wins on dispatch overhead; the auto
#: dispatcher only reaches for devices at or above it.
SHARDED_EXTRACT_MIN_POINTS = 8192

_SHARDED_FNS: dict | None = None


def _sharded_fns() -> dict:
    """Lazily built jax closures for the sharded extraction (this module
    stays importable without jax)."""
    global _SHARDED_FNS
    if _SHARDED_FNS is None:
        import jax
        import jax.numpy as jnp

        def _chunk(all_o, blk, eps):
            return chunk_dominated(all_o, blk, eps, xp=jnp)

        _SHARDED_FNS = {
            "jax": jax,
            "jnp": jnp,
            # per-shard local pass (one block per shard vs itself), vmapped
            # over the shard axis; under a NamedSharding placement XLA
            # partitions the shards across devices
            "local": jax.jit(jax.vmap(_chunk, in_axes=(0, 0, None))),
            # cross-shard refinement: per-device survivor blocks vs ALL rows
            # (the row matrix is replicated, the survivor axis is sharded)
            "refine": jax.jit(jax.vmap(_chunk, in_axes=(None, 0, None))),
            # the same passes as explicit pmaps for runtimes whose
            # jax.sharding surface is incomplete
            "local_pmap": jax.pmap(_chunk, in_axes=(0, 0, None)),
            "refine_pmap": jax.pmap(_chunk, in_axes=(None, 0, None)),
        }
    return _SHARDED_FNS


def nondominated_mask_sharded(objs, eps: float = PARETO_EPS,
                              chunk: int | None = None,
                              mode: str = "auto", mesh=None) -> np.ndarray:
    """Device-sharded :func:`nondominated_mask`: the jitted map-reduce
    dominance pass for lattice-scale frontiers.

    The rows are split into one contiguous shard per visible device and the
    verdict is computed in two exact phases, both running the shared
    :func:`chunk_dominated` predicate with ``xp=jax.numpy``:

      1. *per-shard local prefilter* — each shard walks its rows in blocks,
         testing each block against itself; a row dominated inside its own
         block is dominated, full stop (the witness is a real row).  The
         shard axis is placed with a ``Mesh``/``NamedSharding`` (``mode=
         "jit"``) or ``jax.pmap`` (``mode="pmap"``; ``"auto"`` resolves
         through the engine's capability-probed dispatcher), so the
         quadratic work parallelizes across devices;
      2. *cross-shard refinement* — the gathered local survivors are
         re-tested against **all** rows, survivor axis sharded over the same
         devices, row matrix replicated.  Eps-band dominance is not
         transitive, so testing survivors only against other survivors would
         not be exact; testing against every row is, because every point a
         shard eliminated locally already has a real dominating witness.

    The result is bit-identical to the host :func:`nondominated_mask` — same
    :data:`PARETO_EPS` band, same verdict per row, same output order — on 1
    device and on N devices; only the wall-clock changes.

    ``mesh`` (``"jit"`` mode) is the 1-D device mesh to place the shard axis
    over — pass the mesh a sweep evaluated on so extraction honors the same
    device subset; default is the repo's shared sweep mesh over every
    visible device (:func:`repro.parallel.sharding.spec_sweep_mesh`)."""
    objs = _as_matrix(objs)
    n, k = objs.shape
    if n == 0:
        return np.ones(0, dtype=bool)
    from . import engine as E          # lazy: the one mode dispatcher
    mode = E.resolve_sharded_mode(mode)
    fns = _sharded_fns()
    jax, jnp = fns["jax"], fns["jnp"]
    from ..runtime import enable_x64

    if mode == "jit" and mesh is None:
        # the shared 1-D placement the sharded sweeps use, not an ad-hoc one
        from ..parallel.sharding import spec_sweep_mesh
        mesh = spec_sweep_mesh()
    n_dev = (int(mesh.devices.size) if mesh is not None
             else len(jax.devices()))
    # Every call brings its own row count (each slice of a sweep has one).
    # Shapes follow a power-of-two bucket of it instead, so each jitted pass
    # compiles once per bucket, not once per call.
    n_b = 1 << (n - 1).bit_length()
    m = -(-n_b // n_dev)                             # rows per shard
    c = int(chunk) if chunk else max(64, min(1024, m))
    m_p = -(-m // c) * c                             # fixed block shapes
    # +inf padding is inert under the eps band: an inf row never dominates a
    # finite row, and pad verdicts are sliced off before they are read.
    padded = np.concatenate([objs, np.full((m_p * n_dev - n, k), np.inf)])
    shards = padded.reshape(n_dev, m_p, k)

    dominated = np.empty(n_dev * m_p, dtype=bool)
    with enable_x64():
        eps_j = jnp.asarray(eps, dtype=jnp.float64)
        if mode == "jit":
            from jax.sharding import NamedSharding, PartitionSpec
            row_sharded = NamedSharding(mesh,
                                        PartitionSpec(mesh.axis_names[0]))
            shards_dev = jax.device_put(jnp.asarray(shards), row_sharded)
            local = fns["local"]
            refine = fns["refine"]
            blocks = [shards_dev[:, s:s + c] for s in range(0, m_p, c)]
        else:
            local = fns["local_pmap"]
            refine = fns["refine_pmap"]
            blocks = [shards[:, s:s + c] for s in range(0, m_p, c)]
        parts = [local(blk, blk, eps_j) for blk in blocks]
        dominated[:] = np.concatenate(
            [np.asarray(p) for p in parts], axis=1).reshape(n_dev * m_p)
        dominated = dominated[:n]

        survivors = np.flatnonzero(~dominated)
        all_rows = jnp.asarray(padded[:n_b])
        if mode == "jit":
            all_rows = jax.device_put(
                all_rows, NamedSharding(mesh, PartitionSpec()))  # replicated
        # survivor blocks sized so each device's comparison masks fit its
        # slice of the memory budget
        cr = max(64, pareto_chunk_size(
            n_b, k, DEFAULT_PARETO_BUDGET_BYTES // n_dev))
        stride = n_dev * cr
        for start in range(0, survivors.size, stride):
            idx = survivors[start:start + stride]
            blk = objs[idx]
            if blk.shape[0] < stride:                # keep one traced shape
                blk = np.concatenate(
                    [blk, np.full((stride - blk.shape[0], k), np.inf)])
            blk = blk.reshape(n_dev, cr, k)
            if mode == "jit":
                blk = jax.device_put(jnp.asarray(blk), row_sharded)
            verdict = np.asarray(refine(all_rows, blk, eps_j))
            dominated[idx] = verdict.reshape(stride)[:idx.size]
    return ~dominated


def auto_mask_shards(n_rows: int) -> bool:
    """Whether :func:`nondominated_mask_auto` takes the device-sharded pass
    for ``n_rows`` rows: at or above the payoff point, on several
    devices."""
    if n_rows < SHARDED_EXTRACT_MIN_POINTS:
        return False
    import jax
    return len(jax.devices()) > 1


def nondominated_mask_auto(objs, eps: float = PARETO_EPS) -> np.ndarray:
    """Host mask below the sharding payoff point (or on a single device),
    the device-sharded map-reduce above it.  Both produce the
    same bits, so callers may switch freely on scale."""
    objs = _as_matrix(objs)
    if auto_mask_shards(objs.shape[0]):
        return nondominated_mask_sharded(objs, eps)
    return nondominated_mask(objs, eps)


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


def merged_pareto_indices(parent_idx: Sequence[int],
                          objs: Sequence[Sequence[float]],
                          mask_fn: Callable[[np.ndarray], np.ndarray]
                          | None = None) -> list[int]:
    """:func:`pareto_indices` over a pool assembled from several lattice
    *slices* (the incremental re-synthesis merge): candidate ``i`` carries the
    flat index ``parent_idx[i]`` of the design point in the parent lattice.

    Rows are visited in ascending parent-flat-index order before extraction,
    so the near-duplicate collapse keeps the *same representative* a cold
    full-lattice pass would keep (that pass visits points in flat order) — no
    matter how the pool was partitioned into slices or in which order the
    slices arrived.  Returns positions into the pool as given, frontier
    sorted by objective tuple, exactly like :func:`pareto_indices`.  A pool
    whose slices are disjoint in parent index (the incremental contract)
    therefore merges bit-identically to extracting the union in one pass."""
    parent_idx = np.asarray(parent_idx, dtype=np.int64)
    objs = list(objs)
    if len(parent_idx) != len(objs):
        raise ValueError("parent_idx must match objs one-to-one")
    order = np.argsort(parent_idx, kind="stable")
    picked = pareto_indices([objs[int(j)] for j in order], mask_fn=mask_fn)
    return [int(order[p]) for p in picked]


def pareto_front(items: Iterable[T], objectives: Callable[[T], Sequence[float]]
                 ) -> list[T]:
    """Filter ``items`` to the non-dominated set, stably ordered by the first
    objective."""
    items = list(items)
    objs = [objectives(it) for it in items]
    return [items[i] for i in pareto_indices(objs)]


def scalarize(weights: Sequence[float], objectives: Sequence[float],
              refs: Sequence[float]) -> float:
    """Weighted-sum scalarization with reference normalization (used to pick a
    single design for a PPA preference)."""
    return sum(w * (o / max(r, 1e-30))
               for w, o, r in zip(weights, objectives, refs))


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
