"""The comparison that decides a run's ``correct``.

Each served answer is set beside the plain reference's answer for the same
spec (:mod:`reference.lattice`): the design sequence of every frontier list
must match exactly (membership and order), and every PPA field of every
matched point is compared.  Three numbers come out, each with its limit:

  ``order_mismatches``   answers whose frontier or explored design sequence
                         differs from the reference's (limit 0);
  ``field_mismatches``   non-float fields (latency, meets-timing, the design
                         itself) that differ on matched points (limit 0);
  ``ppa_max_rel``        the largest relative difference of any float field
                         of any matched point (limit :data:`PPA_MAX_REL`).

``flat_ppa`` and the field-by-field walk are copied from the program's
one-off chip smoke check, with the design compared by every field rather
than by its display name (two designs that differ only in OFU retiming share
a name).
"""

from __future__ import annotations

import dataclasses
import math

#: Limit on ``ppa_max_rel``.  Set from readings on a TPU v5e: sound runs of
#: the float64 roll-up read at most about 2e-14 over a dozen seeds, and the
#: control (the roll-up with the float64 scope switched off, so in float32)
#: reads about 1e-7 or more; see PERF.md for the readings.
PPA_MAX_REL = 1e-9


def design_key(d) -> tuple:
    """Every field that identifies a design point (not its audit trail)."""
    cell = d.approx_cell
    return (d.memcell.value, d.multmux.value, d.csa.rho, d.csa.reorder,
            d.csa.retimed, d.csa.split, d.ofu_pipe_stages,
            d.ofu_retimed_into_sa, d.fuse_tree_sa, d.fuse_sa_ofu,
            tuple(d.ofu_precisions or ()), tuple(d.align_fp or ()),
            None if cell is None or cell.is_exact() else cell.name)


def reference_design(d, spec):
    """The reference's own design object for a served design ``d`` of
    ``spec`` (a :class:`reference.macro.MacroSpec`), field by field."""
    from reference import subcircuits as sc
    from reference.csa import CSADesign
    from reference.macro import MacroDesign
    cell = d.approx_cell
    if cell is not None:
        cell = next(c for c in sc.APPROX_CELLS if c.name == cell.name)
    return MacroDesign(
        spec=spec, memcell=sc.MemCellKind(d.memcell.value),
        multmux=sc.MultMuxKind(d.multmux.value),
        csa=CSADesign(rho=d.csa.rho, reorder=d.csa.reorder,
                      retimed=d.csa.retimed, split=d.csa.split),
        ofu_pipe_stages=d.ofu_pipe_stages,
        ofu_retimed_into_sa=d.ofu_retimed_into_sa,
        fuse_tree_sa=d.fuse_tree_sa, fuse_sa_ofu=d.fuse_sa_ofu,
        ofu_precisions=d.ofu_precisions, align_fp=d.align_fp,
        approx_cell=cell)


def flat_ppa(p) -> dict:
    """Every compared field of a MacroPPA as ``name -> value``."""
    out = {"design": design_key(p.design), "fmax_hz": p.fmax_hz,
           "area_um2": p.area_um2, "latency_cycles": p.latency_cycles,
           "tops_1b": p.tops_1b, "tops_per_mm2_1b": p.tops_per_mm2_1b,
           "meets_timing": p.meets_timing}
    for f in dataclasses.fields(p.paths):
        out[f"paths.{f.name}"] = getattr(p.paths, f.name)
    for group in ("area_breakdown", "e_cycle_fj", "tops_per_w_1b"):
        for k, v in getattr(p, group).items():
            out[f"{group}.{k}"] = v
    return out


def rel_diff(got: float, ref: float) -> float:
    if got == ref:
        return 0.0
    if not (math.isfinite(got) and math.isfinite(ref)):
        return math.inf
    return abs(got - ref) / max(abs(ref), 2.2250738585072014e-308)


class Comparison:
    """Accumulates the three compared numbers over many answers."""

    def __init__(self):
        self.answers = 0
        self.points = 0
        self.order_mismatches = 0
        self.field_mismatches = 0
        self.ppa_max_rel = 0.0
        self.worst_field = ""
        self.notes: list[str] = []

    def add_point(self, got, ref) -> None:
        g, r = flat_ppa(got), flat_ppa(ref)
        self.points += 1
        if g.keys() != r.keys():
            self.field_mismatches += 1
            self._note(f"field sets differ: {sorted(g.keys() ^ r.keys())}")
            return
        for k, rv in r.items():
            gv = g[k]
            if isinstance(rv, float) and isinstance(gv, float):
                rel = rel_diff(gv, rv)
                if rel > self.ppa_max_rel:
                    self.ppa_max_rel, self.worst_field = rel, k
            elif gv != rv:
                self.field_mismatches += 1
                self._note(f"{k}: served {gv!r}, reference {rv!r}")

    def add_answer(self, label: str, got, ref) -> None:
        """One served answer (frontier + explored) against the reference."""
        self.answers += 1
        same = got.n_evaluated == ref.n_evaluated
        for name in ("frontier", "explored"):
            gs, rs = getattr(got, name), getattr(ref, name)
            if [design_key(p.design) for p in gs] != \
                    [design_key(p.design) for p in rs]:
                same = False
                continue
            for x, y in zip(gs, rs):
                self.add_point(x, y)
        if not same:
            self.order_mismatches += 1
            self._note(f"{label}: frontier or explored order differs from "
                       "the reference")

    def _note(self, msg: str) -> None:
        if len(self.notes) < 5:
            self.notes.append(msg)

    def checks(self, limit: float = PPA_MAX_REL) -> dict:
        """``name -> {"value", "limit"}`` for the result line."""
        return {"order_mismatches": {"value": self.order_mismatches,
                                     "limit": 0},
                "field_mismatches": {"value": self.field_mismatches,
                                     "limit": 0},
                "ppa_max_rel": {"value": self.ppa_max_rel, "limit": limit}}

    def correct(self, limit: float = PPA_MAX_REL) -> bool:
        return (self.answers > 0 and self.order_mismatches == 0
                and self.field_mismatches == 0 and self.ppa_max_rel <= limit)
