"""Deliberate breaks of the timed path, for the correctness controls only.

A benchmark run never applies one.  ``harness.py --fault <name>`` (and the
CPU tests under ``tests/benchmark``) apply one to show that the comparison
catches it, that is, that ``correct`` comes out false:

  ``f32``       the control: the roll-up's float64 scope switched off, so
                the device computes the lattice in float32 (the precision a
                later change would be tempted to drop to);
  ``alter``     an answer altered where it is produced: every lane's area
                roll-up scaled by 1 + 2**-20 as the engine unpacks it;
  ``exchange``  the exchange between chips left out: after a sharded
                roll-up, every lane takes the result of a lane held by the
                first device instead of its own.
"""

from __future__ import annotations

import contextlib
import dataclasses

FAULTS = ("f32", "alter", "exchange")


def apply(name: str) -> None:
    from repro.core import engine as E

    if name == "f32":
        E.enable_x64 = contextlib.nullcontext
    elif name == "alter":
        unpack = E.unpack_group

        def altered(packed, out):
            ppas = unpack(packed, out)
            for p in ppas:
                p.area = p.area * (1.0 + 2.0 ** -20)
            return ppas

        E.unpack_group = altered
    elif name == "exchange":
        strategy = E.STRATEGIES["sharded-jit"]

        def first_device_only(packed, placement):
            import jax
            out = strategy.run(packed, placement)
            per_dev = max(1, -(-len(packed) // placement.n_dev))
            return jax.tree.map(lambda a: a[[i % per_dev
                                             for i in range(len(a))]], out)

        E.STRATEGIES["sharded-jit"] = dataclasses.replace(
            strategy, run=first_device_only)
    else:
        raise ValueError(f"unknown fault {name!r}; pick from {FAULTS}")
