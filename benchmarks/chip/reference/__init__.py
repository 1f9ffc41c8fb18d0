"""The plain reference the benchmark judges served answers against.

A frozen copy of the program's scalar oracle — the per-point PPA roll-up
(``macro.rollup``), the Algorithm-1 searcher (``searcher.mso_search``), the
subcircuit, CSA and technology models they read, and the host eps-band Pareto
pass — plus a plain enumeration of the design lattice (:mod:`.lattice`).  It
imports nothing of the program under test and uses nothing the program made:
the technology calibration, the lattice and every PPA value are computed here
from the request's spec alone.  Kept under the benchmark so that a change to
the program cannot move the yardstick with it.
"""
