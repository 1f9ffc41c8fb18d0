"""Plain enumeration of the macro design lattice, and the two reference
answers the service is judged against.

The lattice is the mixed-radix product of twelve axes in a fixed order, the
last axis varying fastest: memory cell, mult/mux, CSA compressor share rho,
port reordering, final-RCA retiming, column split, OFU pipeline depth, OFU
retiming, tree/S&A fusion, S&A/OFU fusion, and — when the deployment enables
them — the precision-provisioning plan (SEGA-DCIM) and the approximate
adder-tree cell (OpenACM).  A point is valid unless its mult/mux cannot serve
the spec's MCR.

:func:`sweep` is the exhaustive answer (every valid point rolled up one by
one with :func:`.macro.rollup`, then the eps-band frontier), :func:`search`
the Algorithm-1 answer (:func:`.searcher.mso_search`).
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass

from . import subcircuits as sc
from .csa import CSADesign, valid_splits
from .macro import MacroDesign, MacroPPA, MacroSpec, rollup
from .pareto import pareto_indices
from .searcher import RHO_STEPS, mso_search
from .tech import TechModel

PIPE_STEPS = (0, 1, 2, 3)
FLAGS = (False, True)


def precision_plans(spec: MacroSpec, n_modes: int
                    ) -> tuple[tuple[tuple[int, ...], tuple[str, ...]], ...]:
    """The first ``n_modes`` provisioning plans ``(ints, fps)``: the spec's
    own lists, then one more INT octave, then every FP format, then a second
    INT octave."""
    ints, fps = tuple(spec.int_precisions), tuple(spec.fp_precisions)
    pmax = max(ints)
    all_fps = fps + tuple(f for f in sc.FP_FORMATS if f not in fps)
    plans = ((ints, fps), (ints + (2 * pmax,), fps),
             (ints + (2 * pmax,), all_fps),
             (ints + (2 * pmax, 4 * pmax), all_fps))
    return plans[:n_modes]


@dataclass(frozen=True)
class Lattice:
    """Which optional axes a deployment enumerates."""

    precision_modes: int = 0            # 0: no precision axis
    approx_cells: bool = False          # False: no approximate-cell axis

    def axes(self, spec: MacroSpec) -> list[tuple]:
        out = [tuple(sc.MemCellKind), tuple(sc.MultMuxKind), RHO_STEPS,
               FLAGS, FLAGS, valid_splits(spec.h), PIPE_STEPS, FLAGS, FLAGS,
               FLAGS]
        if self.precision_modes:
            out.append(tuple(enumerate(precision_plans(
                spec, self.precision_modes))))
        if self.approx_cells:
            out.append(sc.APPROX_CELLS)
        return out

    def size(self, spec: MacroSpec) -> int:
        n = 1
        for values in self.axes(spec):
            n *= len(values)
        return n

    def points(self, spec: MacroSpec):
        """Every valid point's axis values, in flat-index order."""
        return (p for p in itertools.product(*self.axes(spec))
                if sc.multmux_valid(p[1], spec.mcr))

    def design(self, spec: MacroSpec, point: tuple) -> MacroDesign:
        (mem, mm, rho, ro, rt, split, pipe, ort, fts, fso) = point[:10]
        kw = {}
        rest = list(point[10:])
        if self.precision_modes:
            k, plan = rest.pop(0)
            if k:
                kw["ofu_precisions"], kw["align_fp"] = plan
        if self.approx_cells:
            cell = rest.pop(0)
            if not cell.is_exact():
                kw["approx_cell"] = cell
        return MacroDesign(
            spec=spec, memcell=mem, multmux=mm,
            csa=CSADesign(rho=rho, reorder=ro, retimed=rt, split=split),
            ofu_pipe_stages=pipe, ofu_retimed_into_sa=ort,
            fuse_tree_sa=fts, fuse_sa_ofu=fso, **kw)

    def designs(self, spec: MacroSpec):
        """Every valid design, in flat-index order."""
        return (self.design(spec, p) for p in self.points(spec))


@dataclass(frozen=True)
class Answer:
    """A reference answer in the shape the service returns one."""

    frontier: tuple[MacroPPA, ...]
    explored: tuple[MacroPPA, ...]
    n_evaluated: int


def objectives(p: MacroPPA) -> tuple[float, float, float]:
    """Energy per cycle at the low INT precision, area, clock period."""
    return (p.e_cycle_fj["int_lo"], p.area_um2, 1.0 / p.fmax_hz)


def _rolled(args) -> list[tuple[float, float, float, bool]]:
    """Objectives and timing verdict of valid points ``lo..hi``."""
    spec, tech, lattice, lo, hi = args
    out = []
    for p in itertools.islice(lattice.points(spec), lo, hi):
        ppa = rollup(lattice.design(spec, p), tech)
        out.append(objectives(ppa) + (ppa.meets_timing,))
    return out


#: Lattices at least this large are rolled up by worker processes.
PARALLEL_POINTS = 65536


def sweep(spec: MacroSpec, tech: TechModel, lattice: Lattice,
          workers: int = 1) -> Answer:
    """The exhaustive answer: ``frontier`` over the points that meet timing
    (over every valid point when none does), ``explored`` over every valid
    point, each sorted by objective tuple.

    Every valid point is rolled up one by one; with ``workers`` > 1 a large
    lattice is split into that many contiguous ranges, each rolled up in its
    own process (they share nothing, so the answer is the same)."""
    valid = list(lattice.points(spec))
    n = len(valid)
    if workers > 1 and n >= PARALLEL_POINTS:
        import multiprocessing
        from concurrent.futures import ProcessPoolExecutor
        cuts = [n * k // workers for k in range(workers + 1)]
        with ProcessPoolExecutor(workers, mp_context=multiprocessing
                                 .get_context("spawn")) as pool:
            rows = [r for part in pool.map(_rolled, [
                (spec, tech, lattice, lo, hi)
                for lo, hi in zip(cuts, cuts[1:])]) for r in part]
    else:
        rows = _rolled((spec, tech, lattice, 0, n))
    objs = [r[:3] for r in rows]
    cand = [i for i, r in enumerate(rows) if r[3]] or list(range(n))
    front = [cand[j] for j in pareto_indices([objs[i] for i in cand])]
    explored = pareto_indices(objs)
    ppa = {i: rollup(lattice.design(spec, valid[i]), tech)
           for i in set(front) | set(explored)}
    return Answer(frontier=tuple(ppa[i] for i in front),
                  explored=tuple(ppa[i] for i in explored),
                  n_evaluated=n)


def search(spec: MacroSpec, tech: TechModel, resolution: int) -> Answer:
    """The Algorithm-1 answer over the preference grid."""
    r = mso_search(spec, None, tech, resolution=resolution)
    return Answer(frontier=r.frontier, explored=r.explored,
                  n_evaluated=r.n_evaluated)
