"""Reduction of a profiler trace to device busy time, idle gaps and kernel
time.

:func:`digest` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
small plain structure, and everything else works on that structure alone, so
a recorded digest checks the arithmetic without a chip:

    {"devices": {"<id>": {"ops": [[name, start_ns, dur_ns], ...],
                          "modules": [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns], ...]}

Device planes are the ``/device:TPU:<id>`` planes (a TPU trace also holds
planes such as ``/device:CUSTOM:Megascale Trace`` that are no chip).  Their
``XLA Ops`` line holds one event per executed operation, ``Async XLA Ops``
one per asynchronous copy in flight, and ``XLA Modules`` one per executed
program.  Both op lines count as the device being busy.  Op names are cut
to the HLO instruction's name (``%fusion.3``), with the target of a custom
call added.  Of the host plane only the harness's clock-sync annotation
(:data:`SYNC`) is kept.
"""

from __future__ import annotations

from pathlib import Path

#: Name of the annotation the harness opens at a known monotonic time, so
#: host spans on the ``time.monotonic`` clock can be placed on the trace's.
SYNC = "bench.clock_sync"

DEVICE_PLANE = "/device:TPU:"
LINES = {"XLA Ops": "ops", "Async XLA Ops": "ops", "XLA Modules": "modules"}


def op_name(hlo: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``%fusion.3``; a custom call
    keeps its target: ``%custom-call.46 (X64Combine)``."""
    name = hlo.split(" = ", 1)[0]
    target = hlo.split('custom_call_target="', 1)
    if len(target) == 2:
        name += f" ({target[1].split(chr(34), 1)[0]})"
    return name


def find_xplane(trace_dir) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def digest(xplane_path) -> dict:
    """The device op and module events and the host annotations of one
    trace file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(xplane_path))
    devices: dict[str, dict] = {}
    host: list = []
    for plane in data.planes:
        name = plane.name
        if name.startswith(DEVICE_PLANE):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = LINES.get(line.name)
                if key == "ops":
                    dev[key].extend([op_name(e.name), e.start_ns,
                                     e.duration_ns] for e in line.events)
                elif key == "modules":
                    dev[key].extend([e.name, e.start_ns, e.duration_ns]
                                    for e in line.events)
            devices[name[len(DEVICE_PLANE):]] = dev
        elif name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events if e.name == SYNC)
    return {"devices": devices, "host": host}


def sync_ns(dig: dict) -> float:
    """Trace time of the clock-sync annotation's start."""
    starts = [s for n, s, _ in dig["host"] if n == SYNC]
    if not starts:
        raise ValueError(f"trace holds no {SYNC!r} annotation")
    return float(starts[0])


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged ``(start, end)`` intervals, clipped to ``[lo, hi]``."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(s + d, hi))
                       for _, s, d in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(dig: dict, lo: float, hi: float) -> dict[str, float]:
    """Per device: nanoseconds in ``[lo, hi]`` in which an op ran."""
    return {dev: sum(e - s for s, e in union(d["ops"], lo, hi))
            for dev, d in dig["devices"].items()}


def idle_share(dig: dict, lo: float, hi: float) -> float | None:
    """1 - busy / window, averaged over the devices; None without any
    device plane."""
    busy = busy_ns(dig, lo, hi)
    if not busy or hi <= lo:
        return None
    return 1.0 - sum(busy.values()) / len(busy) / (hi - lo)


def overlap(s: float, dur: float, lo: float, hi: float) -> float:
    """Nanoseconds of the event ``[s, s + dur)`` inside ``[lo, hi]``."""
    return max(0.0, min(s + dur, hi) - max(s, lo))


def module_ns(dig: dict, match: str, lo: float, hi: float) -> float | None:
    """Summed device nanoseconds, inside ``[lo, hi]``, of the programs whose
    name contains ``match``, over every device; None when no such program
    ran there."""
    total = sum(overlap(s, dur, lo, hi) for d in dig["devices"].values()
                for name, s, dur in d["modules"] if match in name)
    return total or None


def gaps(dig: dict, lo: float, hi: float) -> list[tuple[str, float, float]]:
    """Every idle gap ``(device, start, end)`` of every device in
    ``[lo, hi]``."""
    out = []
    for dev, d in dig["devices"].items():
        t = lo
        for s, e in union(d["ops"], lo, hi):
            if s > t:
                out.append((dev, t, s))
            t = e
        if hi > t:
            out.append((dev, t, hi))
    return out


def label_at(spans: list[tuple[str, float, float]], t: float) -> str:
    """The innermost (shortest) host span open at trace time ``t``, taking
    the work of a pass (``engine.*``, ``cache.*``) before the requests'
    own lifecycle spans, which are open whenever a request waits."""
    open_ = [(name.startswith("request"), e - s, name)
             for name, s, e in spans if s <= t < e]
    return min(open_)[2] if open_ else "no span open"


def breakdown(dig: dict, lo: float, hi: float,
              spans: list[tuple[str, float, float]], top: int = 10) -> dict:
    """The device ops that took most time (seconds, averaged over the
    devices) and the longest idle gaps, each named by the host span that
    was open at its midpoint."""
    n_dev = max(1, len(dig["devices"]))
    per_op: dict[str, float] = {}
    for d in dig["devices"].values():
        for name, s, dur in d["ops"]:
            ns = overlap(s, dur, lo, hi)
            if ns > 0:
                per_op[name] = per_op.get(name, 0.0) + ns
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(gaps(dig, lo, hi), key=lambda g: g[1] - g[2])[:top]
    return {"device_ops": [[name, ns / n_dev / 1e9] for name, ns in ops],
            "idle_gaps": [[label_at(spans, (s + e) / 2), (e - s) / 1e9]
                          for _, s, e in idle]}
