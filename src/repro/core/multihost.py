"""Multi-host spec sharding: the ROADMAP's named next step, landed as a
``register_strategy`` call on the shared execution engine — not a new
execution-path module.

The strategy partitions each vmap group's stacked spec axis over a 2-D
``('host', 'spec')`` mesh (:func:`repro.parallel.sharding.host_spec_mesh`):
one mesh axis per host (``jax.process_count()`` rows), the per-host devices
along the second.  A fused pass splits specs first across hosts and then
across each host's devices — the scaling story the online synthesis
service (:mod:`repro.service`) needs once one host's devices are saturated
by coalesced request batches.  On a single-controller runtime every mesh
device is addressable and one ``NamedSharding`` over both axes places the
lane axis directly; on a genuinely multi-process runtime each process runs
its contiguous lane slice on its local devices and the per-host results
are reassembled with ``multihost_utils.process_allgather`` (process order
== lane order), so no array ever spans non-addressable devices.

On a single-host runtime the host axis has length 1 and the placement
degenerates to the single-host spec sweep — same device set, same per-lane
float64 arithmetic, bit-identical results (the kernel is elementwise per
spec lane; partitioning the lane axis over one mesh axis or two cannot
change per-lane arithmetic).  When the runtime lacks the ``jax.sharding``
surface entirely, :func:`repro.core.engine.resolve_sharded_mode` falls back
from "multihost" to the single-host auto pick (sharded-jit or pmap) — the
single-host path is the fallback, never an error.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from ..runtime import enable_x64

from . import engine as E


def _host_spec_mesh():
    from ..parallel.sharding import host_spec_mesh
    return host_spec_mesh()


def _available() -> bool:
    """Capability probe (hasattr, never a version pin): the NamedSharding
    surface plus a queryable host count; a genuinely multi-process runtime
    additionally needs the cross-process gather primitive — without it the
    engine falls back to the single-host pick instead of crashing on
    non-addressable shards."""
    if not (E._supports_named_sharding() and hasattr(jax, "process_count")):
        return False
    n_proc = jax.process_count()
    if n_proc == 1:
        return True
    if len(jax.devices()) % n_proc:
        return False     # no even host rows -> single-host fallback
    try:
        from jax.experimental import multihost_utils
    except ImportError:
        return False
    return hasattr(multihost_utils, "process_allgather")


def _check_mesh(placement: E.Placement):
    mesh = placement.mesh
    if mesh is None:
        raise ValueError("the 'multihost' strategy needs a mesh "
                         "(use engine.place to resolve one)")
    if tuple(mesh.axis_names) != ("host", "spec"):
        raise ValueError("the 'multihost' strategy needs a ('host', 'spec') "
                         f"mesh, got axes {tuple(mesh.axis_names)}")
    return mesh


def _slice_packed(packed: E.PackedGroup, lo: int, hi: int) -> E.PackedGroup:
    """One host's contiguous slice of a group's lane axis (shared gather
    tuple kept whole — it is lane-invariant)."""
    tabs_s, consts_s, e_ofu_s, e_align_s = packed.operands
    return E.PackedGroup(
        lattices=packed.lattices[lo:hi],
        tables_list=packed.tables_list[lo:hi], csa_i=packed.csa_i,
        ofu_j=packed.ofu_j, idx=packed.idx,
        operands=(tuple(t[lo:hi] for t in tabs_s), consts_s[lo:hi],
                  e_ofu_s[lo:hi], e_align_s[lo:hi]))


def _run_single_controller(packed: E.PackedGroup,
                           placement: E.Placement) -> dict:
    """process_count == 1: every mesh device is addressable, so the lane
    axis is partitioned over *both* mesh axes with one NamedSharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = placement.mesh
    pad, tabs_p, consts_p, e_ofu_p, e_align_p = \
        E._padded_operands(packed, placement.n_dev)

    with enable_x64():
        def put(a, leading_spec: bool):
            axes = ((("host", "spec"),) if leading_spec else (None,)) \
                + (None,) * (np.ndim(a) - 1)
            return jax.device_put(jnp.asarray(a),
                                  NamedSharding(mesh, P(*axes)))

        idx = tuple(put(a, False) for a in packed.idx)
        out = E._eval_kernel_many(idx, tuple(put(t, True) for t in tabs_p),
                                  put(consts_p, True), put(e_ofu_p, True),
                                  put(e_align_p, True))
        out = E.fetch_outputs(out)
    if pad:
        out = jax.tree.map(lambda a: a[:len(packed)], out)
    return out


def _run_multiprocess(packed: E.PackedGroup, placement: E.Placement) -> dict:
    """process_count > 1: a global NamedSharding over the ('host', 'spec')
    mesh would span non-addressable devices, so each process instead runs
    its own contiguous lane slice on its *local* devices (the single-host
    sharded path over a local ('spec',) mesh — every operand is replicated
    host-side by construction, the planner being deterministic) and the
    per-host results are reassembled with ``process_allgather`` in process
    order, which is lane order."""
    from jax.experimental import multihost_utils
    from jax.sharding import Mesh

    n_hosts = placement.mesh.devices.shape[0]
    if n_hosts != jax.process_count():
        # A hand-built mesh whose host axis disagrees with the runtime would
        # make the per-process slices (and the allgather shapes) inconsistent
        # across processes — fail loudly instead of gathering garbage.
        raise ValueError(
            f"multihost mesh has {n_hosts} host rows but the runtime has "
            f"{jax.process_count()} processes; build the mesh with "
            "parallel.sharding.host_spec_mesh on every process")
    me = int(jax.process_index())
    tabs_s, consts_s, e_ofu_s, e_align_s = packed.operands
    pad = (-len(packed)) % n_hosts
    padded = E.PackedGroup(
        lattices=packed.lattices + (packed.lattices[0],) * pad,
        tables_list=packed.tables_list + (packed.tables_list[0],) * pad,
        csa_i=packed.csa_i, ofu_j=packed.ofu_j, idx=packed.idx,
        operands=(tuple(E.pad_lanes(t, pad) for t in tabs_s),
                  E.pad_lanes(consts_s, pad), E.pad_lanes(e_ofu_s, pad),
                  E.pad_lanes(e_align_s, pad)))
    per = len(padded) // n_hosts
    mine = _slice_packed(padded, me * per, (me + 1) * per)

    local_mesh = Mesh(np.asarray(jax.local_devices()), ("spec",))
    local = E.Placement(mode="sharded-jit", mesh=local_mesh,
                        n_dev=int(local_mesh.devices.size))
    out_local = E._run_sharded_jit(mine, local)
    out = jax.tree.map(
        lambda a: np.asarray(multihost_utils.process_allgather(a,
                                                               tiled=True)),
        out_local)
    if pad:
        out = jax.tree.map(lambda a: a[:len(packed)], out)
    return out


def _run_multihost(packed: E.PackedGroup, placement: E.Placement) -> dict:
    """The vmapped kernel with its spec axis partitioned over the
    ``('host', 'spec')`` mesh — specs split across hosts, then across each
    host's devices."""
    _check_mesh(placement)
    if jax.process_count() == 1:
        return _run_single_controller(packed, placement)
    return _run_multiprocess(packed, placement)


#: The ROADMAP contract, verbatim: multi-host spec sharding is a
#: register_strategy call on the engine.
MULTIHOST = E.register_strategy(
    E.Strategy("multihost", _available, _run_multihost, sharded=True,
               default_mesh=_host_spec_mesh))
