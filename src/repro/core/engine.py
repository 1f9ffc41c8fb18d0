"""Unified synthesis execution engine: one plan → place → execute → extract
pipeline shared by every way the compiler runs Algorithm 1's evaluation.

Before this layer existed the repo had four execution paths — the scalar
per-point hierarchy, the single-spec batched lattice (:mod:`repro.core.
batched`), the multi-spec vmapped pass (:mod:`repro.core.multispec`) and the
device-sharded pass (:mod:`repro.core.shardspec`) — and the last three each
re-implemented spec grouping, operand packing, lane padding, device placement
and the numpy frontier tail.  This module is the single owner of that
pipeline; the path modules are now thin strategies over it:

  plan      characterize specs (``DesignLattice`` + ``SpecTables``) and
            bucket them into vmap groups by lattice signature
            (:func:`group_key` / :func:`plan`);
  place     resolve an execution mode by capability probe (``hasattr``,
            never version pins) and bind it to a device mesh
            (:func:`place` / :class:`Placement`);
  execute   pack each group's operands (:func:`pack_group`), run the shared
            jitted float64 kernel under the placed strategy, and finish with
            the shared single-spec numpy tail (:func:`unpack_group`) —
            per-spec results are bit-identical across every strategy because
            the kernel is elementwise per spec lane (:func:`execute`);
  extract   the frontier tail: a survivor mask (host predicate, on-device
            chunked, or device-sharded map-reduce — all computing the same
            eps-band verdicts) followed by the exact dedup/order pass
            (:func:`extract_frontier`).

Execution strategies live in a registry (:data:`STRATEGIES`,
:func:`register_strategy`), so scaling further is a strategy registration,
not a fifth reimplementation of the pipeline:

  ``"jit"``          one spec, unbatched kernel launch (the
                     :mod:`repro.core.batched` path);
  ``"vmap"``         a fused same-shape group on one device
                     (:mod:`repro.core.multispec`);
  ``"sharded-jit"``  the vmapped group with its spec axis partitioned by a
                     ``Mesh``/``NamedSharding`` over a ``('spec',)`` mesh
                     (:mod:`repro.core.shardspec`'s preferred mode);
  ``"pmap"``         the vmapped group folded over a leading device axis —
                     the fallback for runtimes whose ``jax.sharding``
                     surface is incomplete;
  ``"multihost"``    the spec axis partitioned over a ``('host', 'spec')``
                     mesh — one mesh axis per host — registered by
                     :mod:`repro.core.multihost` (the ROADMAP's multi-host
                     next step, landed as exactly the promised
                     ``register_strategy`` call).

Execution is observable: :func:`add_execute_hook` registers a callback fired
once per :func:`execute` call with the plan being run.  The online synthesis
service (:mod:`repro.service`) counts engine executions through this hook to
prove cache hits and request coalescing never re-enter the engine.  With
tracing on (:mod:`repro.obs`), :func:`execute` records one ``engine.execute``
span per call with a child per group for each of its steps — ``execute.pack``
(host operand packing), ``execute.wait`` (device compute and queueing),
``execute.fetch`` (device-to-host copy) and ``execute.unpack`` (the numpy
tail); the rest of ``engine.execute`` is operand upload and launch.
:func:`extract_frontier` records ``extract.mask`` around each survivor mask,
tagged with its row counts.
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Any, Callable, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from ..obs import tracer
from ..runtime import enable_x64

from . import batched as B
from . import subcircuits as sc
from .batched import BatchedPPA, DesignLattice, SpecTables
from .macro import MacroSpec
# Chunk sizing lives with the shared Pareto predicate; re-exported here for
# strategies sizing frontier chunks against the accelerator budget.
from .pareto import pareto_chunk_size, pareto_indices  # noqa: F401
from .pareto import (SHARDED_EXTRACT_MIN_POINTS, auto_mask_shards,
                     nondominated_mask, nondominated_mask_auto,
                     nondominated_mask_counts)
from .tech import TechModel

# ---------------------------------------------------------------------------
# Shared kernels: the single-spec kernel, vmapped / pmapped over a spec axis
# ---------------------------------------------------------------------------

# The single-spec kernel, vmapped over a leading spec axis: the gather-index
# tuple is shared (in_axes=None) while every table, constant and mode array
# carries one row per spec.  Gathers and adds are elementwise under batching,
# so per-spec lanes compute bit-identically to the unbatched kernel.
_eval_kernel_many = jax.jit(
    jax.vmap(B._eval_kernel, in_axes=(None, 0, 0, 0, 0)))

# The pmap fallback: the same vmapped kernel, mapped over a leading device
# axis.  Both maps are elementwise per spec lane so per-lane arithmetic is
# the unbatched kernel's, bit for bit.
_eval_kernel_pmap = jax.pmap(
    jax.vmap(B._eval_kernel, in_axes=(None, 0, 0, 0, 0)),
    in_axes=(None, 0, 0, 0, 0))


# ---------------------------------------------------------------------------
# Plan: spec grouping + operand packing
# ---------------------------------------------------------------------------


def group_key(lattice: DesignLattice, tables: SpecTables):
    """Specs share a vmap group iff their lattices address identically —
    same registered axes at the same sizes — and their mode axes have equal
    length (mode *names* may differ per spec).  Axis names participate so an
    extended lattice (precision / approx_cell axes enabled) can never fuse
    with a seed lattice that happens to share its flat shape."""
    return (tuple(a.name for a in lattice.axes), lattice.dims,
            lattice.splits, len(tables.modes))


@dataclass(frozen=True)
class PackedGroup:
    """numpy-side operands for one group launch: the shared gather tuple
    (one copy for the whole group) plus every per-spec kernel input stacked
    along a leading spec axis."""

    lattices: tuple[DesignLattice, ...]
    tables_list: tuple[SpecTables, ...]
    csa_i: np.ndarray
    ofu_j: np.ndarray
    idx: tuple[np.ndarray, ...]
    operands: tuple      # (tabs_s, consts_s, e_ofu_s, e_align_s)

    def __len__(self) -> int:
        return len(self.lattices)


def pack_group(lattices: Sequence[DesignLattice],
               tables_list: Sequence[SpecTables]) -> PackedGroup:
    """Pack one vmap group's kernel operands (every strategy — vmap, sharded
    jit, pmap, and the single-spec jit launch — executes from this one
    packing, so the paths cannot drift).  Gather indices come from the
    tables' axis-flattening helpers (``csa_index`` / ``ofu_index``), so an
    optional axis's coordinates reach the kernel as wider gathers into the
    flattened tables — never as new kernel code."""
    lat0, t0 = lattices[0], tables_list[0]
    for lat, tab in zip(lattices, tables_list):
        if not tab.compatible_with(lat):
            raise ValueError(
                f"tables built for axes {[(a.name, a.size) for a in tab.axes]}"
                f" cannot serve lattice axes "
                f"{[(a.name, a.size) for a in lat.axes]}")
    csa_i = np.asarray(t0.csa_index(lat0.rho_i, lat0.ro, lat0.rt, lat0.sp_i,
                                    lat0.apx_i))
    ofu_j = np.asarray(t0.ofu_index(lat0.pipe_i, lat0.prec_i))
    packed = [B._kernel_inputs(t) for t in tables_list]
    tabs_s = tuple(np.stack([p[0][j] for p in packed], dtype=np.float64)
                   for j in range(len(packed[0][0])))
    consts_s = np.stack([p[1] for p in packed], dtype=np.float64)
    e_ofu_s = np.stack([p[2] for p in packed], dtype=np.float64)
    e_align_s = np.stack([p[3] for p in packed], dtype=np.float64)
    idx = (lat0.mem_i, lat0.mm_i, csa_i, ofu_j, lat0.prec_i, lat0.ort,
           lat0.fts, lat0.fso)
    return PackedGroup(lattices=tuple(lattices),
                       tables_list=tuple(tables_list), csa_i=csa_i,
                       ofu_j=ofu_j, idx=idx,
                       operands=(tabs_s, consts_s, e_ofu_s, e_align_s))


def unpack_group(packed: PackedGroup, out: dict) -> list[BatchedPPA]:
    """The shared single-spec numpy tail, applied per spec lane of one
    group's kernel outputs (bit-identity by construction)."""
    return [B._finish(packed.lattices[s], packed.tables_list[s], packed.csa_i,
                      packed.ofu_j, jax.tree.map(lambda a: a[s], out))
            for s in range(len(packed))]


def pad_lanes(arr: np.ndarray, pad: int) -> np.ndarray:
    """Pad the leading spec axis with copies of lane 0 (cheap, NaN-free
    filler — padded lanes are computed and discarded, never compared)."""
    if pad == 0:
        return arr
    return np.concatenate([arr, np.repeat(arr[:1], pad, axis=0)], axis=0)


# ---------------------------------------------------------------------------
# Place: capability-probed mode dispatch + strategy registry
# ---------------------------------------------------------------------------


def _supports_named_sharding() -> bool:
    """Capability probe for the NamedSharding execution path (hasattr, not a
    version pin — the same detection style the distributed tests use)."""
    return (hasattr(jax, "sharding")
            and hasattr(jax.sharding, "Mesh")
            and hasattr(jax.sharding, "NamedSharding")
            and hasattr(jax.sharding, "PartitionSpec")
            and hasattr(jax, "device_put"))


@dataclass(frozen=True)
class Placement:
    """A resolved execution mode bound to its devices."""

    mode: str
    mesh: Any = None
    n_dev: int = 1


@dataclass(frozen=True)
class Strategy:
    """One way to run a packed group: ``run(packed, placement)`` returns the
    kernel outputs as host numpy with a leading spec axis of exactly
    ``len(packed)`` lanes.  ``default_mesh`` (when set) builds the mesh
    :func:`place` binds when the caller passes none — each sharded strategy
    owns its placement geometry instead of :func:`place` special-casing
    names."""

    name: str
    available: Callable[[], bool]
    run: Callable[[PackedGroup, Placement], dict]
    sharded: bool = False
    default_mesh: Callable[[], Any] | None = None


#: The capability-probed strategy registry — scaling the engine further
#: (multi-host meshes, new runtimes) is a :func:`register_strategy` call,
#: not another execution-path module.
STRATEGIES: dict[str, Strategy] = {}


def register_strategy(strategy: Strategy) -> Strategy:
    STRATEGIES[strategy.name] = strategy
    return strategy


#: Public mode names of the device-sharded surface (shardspec + sharded
#: Pareto extraction): "jit" = NamedSharding placement, "pmap" = the
#: fallback, "multihost" = the ('host', 'spec') mesh strategy.
SHARDED_MODES = ("auto", "jit", "pmap", "multihost")

#: Public sharded mode -> engine strategy name.
_SHARDED_STRATEGY = {"jit": "sharded-jit", "pmap": "pmap",
                     "multihost": "multihost"}


def resolve_sharded_mode(mode: str = "auto") -> str:
    """'auto' picks NamedSharding+jit when the runtime has it, else pmap.
    This is the one capability-probed dispatcher every sharded surface
    (spec sweeps and Pareto extraction) resolves through.  "multihost" falls
    back to the single-host auto pick when the multi-host strategy is
    unavailable on this runtime — the fallback contract of the ROADMAP's
    multi-host registration."""
    if mode not in SHARDED_MODES:
        raise ValueError(f"unknown shardspec mode: {mode!r}; "
                         f"pick from {SHARDED_MODES}")
    if mode == "auto":
        return "jit" if STRATEGIES["sharded-jit"].available() else "pmap"
    if mode == "multihost" and not STRATEGIES["multihost"].available():
        return resolve_sharded_mode("auto")
    return mode


def place(mode: str = "auto", mesh=None, *, sharded: bool = False
          ) -> Placement:
    """Resolve an execution mode and bind it to devices.

    ``mode`` is an engine strategy name or ``"auto"``; ``sharded=True`` makes
    "auto" resolve across devices (NamedSharding-jit when the runtime has it,
    else pmap) instead of to the single-device vmap strategy.  A sharded
    strategy with no caller-provided mesh is bound to its own
    ``default_mesh`` (a ``('spec',)`` mesh over every visible device for
    "sharded-jit", a ``('host', 'spec')`` mesh for "multihost"); the pmap
    strategy needs nothing from ``jax.sharding``."""
    if mode == "auto":
        mode = (_SHARDED_STRATEGY[resolve_sharded_mode("auto")] if sharded
                else "vmap")
    if mode not in STRATEGIES:
        raise ValueError(f"unknown engine mode: {mode!r}; "
                         f"pick from {sorted(STRATEGIES)}")
    if not STRATEGIES[mode].available():
        raise ValueError(f"engine mode {mode!r} is not available "
                         "on this runtime")
    if mesh is None and STRATEGIES[mode].default_mesh is not None:
        mesh = STRATEGIES[mode].default_mesh()
    if mesh is not None:
        n_dev = int(mesh.devices.size)
    elif STRATEGIES[mode].sharded:
        n_dev = len(jax.devices())
    else:
        n_dev = 1
    return Placement(mode=mode, mesh=mesh, n_dev=n_dev)


# ---------------------------------------------------------------------------
# Execute: the registered strategies
# ---------------------------------------------------------------------------


def fetch_outputs(out) -> dict:
    """Wait for a strategy's device outputs (``execute.wait``: device
    compute and queueing), then copy each leaf to host numpy
    (``execute.fetch``, tagged with the leaf count and bytes copied)."""
    with tracer.span("execute.wait"):
        out = jax.block_until_ready(out)
    with tracer.span("execute.fetch") as span:
        if span:
            leaves = jax.tree.leaves(out)
            span.set_tag("arrays", len(leaves))
            span.set_tag("bytes", sum(int(a.nbytes) for a in leaves))
        return jax.tree.map(np.asarray, out)


def _run_jit(packed: PackedGroup, placement: Placement) -> dict:
    """Single-spec unbatched launch — the :mod:`repro.core.batched` path."""
    if len(packed) != 1:
        raise ValueError("the 'jit' strategy runs exactly one spec; "
                         "use 'vmap' or a sharded mode for groups")
    tabs_s, consts_s, e_ofu_s, e_align_s = packed.operands
    with enable_x64():
        idx = tuple(jnp.asarray(a) for a in packed.idx)
        out = B._eval_kernel(idx, tuple(jnp.asarray(t[0]) for t in tabs_s),
                             jnp.asarray(consts_s[0]),
                             jnp.asarray(e_ofu_s[0]),
                             jnp.asarray(e_align_s[0]))
        out = fetch_outputs(out)
    return jax.tree.map(lambda a: a[None], out)


def _run_vmap(packed: PackedGroup, placement: Placement) -> dict:
    """One vmapped kernel launch for a group of same-shape specs."""
    tabs_s, consts_s, e_ofu_s, e_align_s = packed.operands
    with enable_x64():
        idx = tuple(jnp.asarray(a) for a in packed.idx)
        out = _eval_kernel_many(idx, tuple(jnp.asarray(t) for t in tabs_s),
                                jnp.asarray(consts_s), jnp.asarray(e_ofu_s),
                                jnp.asarray(e_align_s))
        out = fetch_outputs(out)
    return out


def _padded_operands(packed: PackedGroup, n_dev: int):
    """Pad the ragged spec count of a packed group up to the device count."""
    tabs_s, consts_s, e_ofu_s, e_align_s = packed.operands
    pad = (-len(packed)) % n_dev
    return (pad, tuple(pad_lanes(t, pad) for t in tabs_s),
            pad_lanes(consts_s, pad), pad_lanes(e_ofu_s, pad),
            pad_lanes(e_align_s, pad))


def _run_sharded_jit(packed: PackedGroup, placement: Placement) -> dict:
    """The vmapped kernel with its spec axis partitioned by Mesh/NamedSharding
    over a ``('spec',)`` mesh — the kernel is elementwise per spec lane, so
    partitioning the lane axis cannot change per-lane float64 arithmetic."""
    from jax.sharding import NamedSharding

    from ..parallel.sharding import logical_to_spec, rules_for_mesh
    mesh = placement.mesh
    if mesh is None:
        raise ValueError("the 'sharded-jit' strategy needs a mesh "
                         "(use engine.place to resolve one)")
    pad, tabs_p, consts_p, e_ofu_p, e_align_p = \
        _padded_operands(packed, placement.n_dev)
    rules = rules_for_mesh(mesh)

    with enable_x64():
        def put(a, leading_spec: bool):
            axes = (("spec",) if leading_spec else (None,)) \
                + (None,) * (np.ndim(a) - 1)
            sharding = NamedSharding(mesh, logical_to_spec(axes, rules))
            return jax.device_put(jnp.asarray(a), sharding)

        idx = tuple(put(a, False) for a in packed.idx)
        out = _eval_kernel_many(idx, tuple(put(t, True) for t in tabs_p),
                                put(consts_p, True), put(e_ofu_p, True),
                                put(e_align_p, True))
        out = fetch_outputs(out)
    if pad:
        out = jax.tree.map(lambda a: a[:len(packed)], out)
    return out


def _run_pmap(packed: PackedGroup, placement: Placement) -> dict:
    """The vmapped kernel folded over a leading device axis — the fallback
    for runtimes whose ``jax.sharding`` surface is incomplete."""
    n_dev = placement.n_dev
    pad, tabs_p, consts_p, e_ofu_p, e_align_p = \
        _padded_operands(packed, n_dev)
    per_dev = (len(packed) + pad) // n_dev

    def fold(a):
        a = np.asarray(a)
        return a.reshape((n_dev, per_dev) + a.shape[1:])

    with enable_x64():
        idx = tuple(jnp.asarray(a) for a in packed.idx)
        out = _eval_kernel_pmap(idx, tuple(fold(t) for t in tabs_p),
                                fold(consts_p), fold(e_ofu_p),
                                fold(e_align_p))
        # unfold (devices, specs/device) -> specs on the host copy: a numpy
        # view, and no further jax dispatch on this branch
        out = jax.tree.map(
            lambda a: a.reshape((n_dev * per_dev,) + a.shape[2:]),
            fetch_outputs(out))
    if pad:
        out = jax.tree.map(lambda a: a[:len(packed)], out)
    return out


def _spec_sweep_mesh():
    from ..parallel.sharding import spec_sweep_mesh
    return spec_sweep_mesh()


register_strategy(Strategy("jit", lambda: True, _run_jit))
register_strategy(Strategy("vmap", lambda: hasattr(jax, "vmap"), _run_vmap))
register_strategy(Strategy("sharded-jit", _supports_named_sharding,
                           _run_sharded_jit, sharded=True,
                           default_mesh=_spec_sweep_mesh))
register_strategy(Strategy("pmap", lambda: hasattr(jax, "pmap"), _run_pmap,
                           sharded=True))


# ---------------------------------------------------------------------------
# The plan object + end-to-end execution
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ExecutionPlan:
    """A placed evaluation of N specs: characterized lattices/tables, the
    vmap grouping, and the resolved device placement."""

    lattices: tuple[DesignLattice, ...]
    tables: tuple[SpecTables, ...]
    groups: tuple[tuple[int, ...], ...]
    placement: Placement

    def __len__(self) -> int:
        return len(self.lattices)


def plan_for(lattices: Sequence[DesignLattice],
             tables: Sequence[SpecTables], mode: str = "auto", mesh=None,
             sharded: bool = False,
             placement: Placement | None = None) -> ExecutionPlan:
    """Group already-characterized specs into an :class:`ExecutionPlan`.
    An already-resolved ``placement`` skips the :func:`place` call (callers
    that time planning and placement as separate phases resolve it first)."""
    groups: dict[tuple, list[int]] = {}
    for i, (lat, tab) in enumerate(zip(lattices, tables)):
        groups.setdefault(group_key(lat, tab), []).append(i)
    if placement is None:
        placement = place(mode, mesh, sharded=sharded)
    return ExecutionPlan(lattices=tuple(lattices), tables=tuple(tables),
                         groups=tuple(tuple(m) for m in groups.values()),
                         placement=placement)


def plan(specs: Sequence[MacroSpec], tech: TechModel,
         memcells: tuple[sc.MemCellKind, ...] | None = None,
         mode: str = "auto", mesh=None, sharded: bool = False,
         config: "B.LatticeConfig | None" = None) -> ExecutionPlan:
    """Characterize every spec and bucket them into vmap groups — the one
    grouping every execution path shares, so all paths group identically.
    ``config`` selects the lattice axis set (seed when None); ``memcells``
    overrides its memcell axis (the historical argument)."""
    if config is None:
        config = B.seed_config(memcells)
    elif memcells is not None:
        config = config.with_memcells(memcells)
    lattices = [DesignLattice.enumerate(s, config=config) for s in specs]
    tables = [SpecTables(s, tech, config=config) for s in specs]
    return plan_for(lattices, tables, mode=mode, mesh=mesh, sharded=sharded)


#: Observers fired once per :func:`execute` call with the plan being run —
#: the instrumentation point the synthesis service and its tests use to
#: count engine entries (a cache hit or coalesced duplicate must cause
#: zero of them).
_EXECUTE_HOOKS: list[Callable[[ExecutionPlan], None]] = []


def add_execute_hook(hook: Callable[[ExecutionPlan], None]
                     ) -> Callable[[ExecutionPlan], None]:
    """Register an observer called with every :class:`ExecutionPlan` the
    engine runs.  Returns ``hook`` so it can be used as a decorator."""
    _EXECUTE_HOOKS.append(hook)
    return hook


def remove_execute_hook(hook: Callable[[ExecutionPlan], None]) -> None:
    _EXECUTE_HOOKS.remove(hook)


#: Observers fired once per :func:`execute` call with the plan and the
#: wall-clock seconds the pass took — the feedback signal the async serving
#: front's batching window adapts to (a slow engine grows batches instead
#: of queues).
_LATENCY_HOOKS: list[Callable[[ExecutionPlan, float], None]] = []


def add_latency_hook(hook: Callable[[ExecutionPlan, float], None]
                     ) -> Callable[[ExecutionPlan, float], None]:
    """Register an observer called with ``(plan, elapsed_s)`` after every
    :func:`execute` pass completes.  Returns ``hook`` so it can be used as
    a decorator."""
    _LATENCY_HOOKS.append(hook)
    return hook


def remove_latency_hook(hook: Callable[[ExecutionPlan, float], None]) -> None:
    _LATENCY_HOOKS.remove(hook)


def execute(p: ExecutionPlan
            ) -> list[tuple[DesignLattice, SpecTables, BatchedPPA]]:
    """Run every group of the plan under its placed strategy and finish with
    the shared numpy tail.  Results are returned in input order and are
    bit-identical per spec across every strategy."""
    for hook in tuple(_EXECUTE_HOOKS):
        hook(p)
    t0 = time.perf_counter()
    strategy = STRATEGIES[p.placement.mode]
    out: list = [None] * len(p)
    with tracer.span("engine.execute",
                     tags={"mode": p.placement.mode, "n_specs": len(p),
                           "n_groups": len(p.groups),
                           "n_dev": p.placement.n_dev}):
        for members in p.groups:
            with tracer.span("execute.pack"):
                packed = pack_group([p.lattices[i] for i in members],
                                    [p.tables[i] for i in members])
            raw = strategy.run(packed, p.placement)
            with tracer.span("execute.unpack"):
                ppas = unpack_group(packed, raw)
            for i, ppa in zip(members, ppas):
                out[i] = (p.lattices[i], p.tables[i], ppa)
    elapsed = time.perf_counter() - t0
    for hook in tuple(_LATENCY_HOOKS):
        hook(p, elapsed)
    return out


# ---------------------------------------------------------------------------
# Extract: the shared frontier tail
# ---------------------------------------------------------------------------


def _host_mask(mask_fn, n_rows: int):
    """The host walk ``mask_fn`` runs for an ``n_rows``-row mask, as a call
    that also returns its counts
    (:func:`repro.core.pareto.nondominated_mask_counts`), or None where
    ``mask_fn`` computes it on the devices (the sharded map-reduce, or the
    one-device chunked pass).  The two dispatchers go to the devices at the
    sharding payoff point."""
    from . import shardspec
    fn = getattr(mask_fn, "func", mask_fn)       # unwrap functools.partial
    keywords = getattr(mask_fn, "keywords", {})
    if fn is nondominated_mask or (fn is nondominated_mask_auto
                                   and not auto_mask_shards(n_rows)):
        return functools.partial(nondominated_mask_counts, **keywords)
    if fn is shardspec._sharded_extract and \
            n_rows < SHARDED_EXTRACT_MIN_POINTS:
        return nondominated_mask_counts
    return None


def extract_frontier(objs, mask_fn: Callable[[np.ndarray], np.ndarray]
                     ) -> list[int]:
    """The numpy frontier tail every sweep shares: a survivor mask from
    ``mask_fn`` (host :func:`repro.core.pareto.nondominated_mask`, the
    on-device chunked :func:`repro.core.batched.pareto_mask`, or the
    device-sharded :func:`repro.core.pareto.nondominated_mask_sharded` — all
    bit-identical by construction), then the exact dedup/order pass of
    :func:`repro.core.pareto.pareto_indices` on the survivors.  Returns
    indices into ``objs`` sorted by objective tuple.  The mask runs inside
    an ``extract.mask`` span tagged with its ``rows`` and ``where`` it ran
    (``host`` or ``devices``); a host mask also tags the ``distinct`` rows
    left after its duplicate collapse and the ``survivors`` it refined."""
    objs = np.asarray(objs, dtype=np.float64)
    with tracer.span("extract.mask") as span:
        host = _host_mask(mask_fn, len(objs))
        if host is None:
            mask = np.asarray(mask_fn(objs)).astype(bool)
        else:
            mask, distinct, refined = host(objs)
        if span:
            span.set_tag("rows", len(objs))
            span.set_tag("where", "devices" if host is None else "host")
            if host is not None:
                span.set_tag("distinct", distinct)
                span.set_tag("survivors", refined)
    survivors = np.flatnonzero(mask)
    order = pareto_indices([tuple(o) for o in objs[mask]])
    return [int(survivors[i]) for i in order]


# The multi-host strategy registers itself against this module's registry;
# importing it last keeps the registration a plain `register_strategy` call
# (the ROADMAP contract) without a circular-import dance.
from . import multihost as _multihost  # noqa: E402,F401
