#!/usr/bin/env python3
"""Chip benchmark of the DCIM synthesis service: one run of one cell.

    python benchmarks/chip/harness.py --workload <cell> --seed <n> \\
        --seconds <run_seconds> --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) is one configuration file
(``configs/``, a deployment of the service) under one traffic mix
(``traffic/``).  A run builds a fresh ``SynthesisService`` with its own new
artifact cache directory behind a ``ServiceFrontend``, warms exactly the
programs the cell's traffic runs (set-up), then for ``--seconds`` drives the
frontend with the cell's requests: ``ServiceFrontend.submit`` through the
cache tiers and the fused engine pass, the path users get.  After the window
it judges what was served against the plain reference (``reference/``) and
prints, as the last line of standard output, one JSON object:

    {"correct", "attempted", "failed", "metrics", "device",
     ["breakdown",] "checks"}

With ``--trace 0`` the metrics are the cell's end-to-end metrics, taken with
the program's tracing off; with ``--trace 1`` the window runs under the
program's spans and the JAX profiler, and the metrics are the cell's
per-layer metrics (one reader each, ``metrics/<name>.py``).

Set-up, by traffic (``traffic.py``): every macro family of the spec space
has its roll-up programs warmed at every lane count a pass of the traffic
can give its group; an open loop serves one spec of each family and its hot
pool; a closed loop with ``recal`` serves its pool and one spec beside it as
cold sweeps, then that spec under ``warm_factor``, which compiles the
incremental re-synthesis path.  Each request counts its own spec's lattice
points, and is judged under its own tech: the reference's calibration with
the request's recalibrated field scaled by the same factor.

The run refuses (exit 2, no result) when JAX finds no TPU or fewer chips than
the cell asks for.  ``--fault`` breaks the timed path on purpose, for the
correctness controls (``faults.py``); benchmark runs never pass it.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import queue  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import compare  # noqa: E402
import discovery  # noqa: E402
import tracefile  # noqa: E402
from runrecord import Request, Run  # noqa: E402
from traffic import Traffic  # noqa: E402

#: How long past the window's close a due answer is waited for.
LATE_WAIT_S = 60.0

#: Served search answers compared with the reference per run, at most
#: (drawn from the seed); every served sweep's frontier points are
#: compared, and this many whole sweeps against the reference sweep.
SEARCH_SAMPLE = 600
SWEEP_SAMPLE = 1

#: Processes the reference sweep is rolled up in, after the window.
REFERENCE_WORKERS = max(1, min(8, (os.cpu_count() or 2) // 2))


class Refused(RuntimeError):
    """The run cannot be measured here (no chip, unknown device)."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", default=None,
                    help="break the timed path on purpose (controls only)")
    ap.add_argument("--out", default=None,
                    help="directory to keep the trace digest in")
    return ap.parse_args(argv)


def check_device(chips: int) -> None:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: jax.devices()[0] is {devs[0].platform!r}")
    if len(devs) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX finds "
                      f"{len(devs)}")
    peaks = json.loads((HERE / "peaks.json").read_text())["devices"]
    if devs[0].device_kind not in peaks:
        raise Refused(f"device kind {devs[0].device_kind!r} is not in "
                      "peaks.json")


# ---------------------------------------------------------------------------
# The window's two loops
# ---------------------------------------------------------------------------


def lattice_points(cfg: dict, families: list[dict]) -> dict:
    """Lattice points of one spec of each family, by ``(h, w)``."""
    from reference import lattice as L
    from reference import macro as RM
    lattice = L.Lattice(int(cfg["lattice"]["precision_modes"]),
                        bool(cfg["lattice"]["approx_cells"]))
    return {(f["h"], f["w"]): lattice.size(RM.MacroSpec(h=f["h"], w=f["w"]))
            for f in families}


def batching(traffic: Traffic, chips: int) -> tuple[int, list[int]]:
    """The frontend's batch limit and the lane counts a fused pass can give
    one family's group: a closed loop's pass holds one request per client,
    an open loop's any count up to the limit; where families mix, a pass
    splits into groups of any count up to the limit."""
    if traffic.loop == "closed":
        max_batch = traffic.clients(chips)
        if len(traffic.families) == 1:
            return max_batch, [max_batch]
    else:
        max_batch = int(traffic.traffic["frontend"]["max_batch"])
    return max_batch, list(range(1, max_batch + 1))


def open_loop(front, dep, arrivals, t0: float, seconds: float,
              points: dict) -> list[Request]:
    """Submit each arrival at its due time, whatever the system's state."""
    out = []
    for a in arrivals:
        due = t0 + a.due_s
        wait = due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        r = Request(spec=a.spec, due=due,
                    points=points[a.spec["h"], a.spec["w"]])
        r.submitted = time.monotonic()
        r.ticket = front.submit(dep.request(a.spec))
        out.append(r)
    rest = t0 + seconds - time.monotonic()
    if rest > 0:
        time.sleep(rest)
    return out


def latency_halves(requests, t0, t_end, gave_up):
    """Median latency of the requests due in each half of the window: a
    backlog that grows through the window shows as a later half slower."""
    import statistics
    mid = (t0 + t_end) / 2
    out = []
    for lo, hi in ((t0, mid), (mid, t_end)):
        lat = [(r.done if r.served else gave_up) - r.due
               for r in requests if lo <= r.due < hi]
        out.append(round(statistics.median(lat), 6) if lat else None)
    return out


def collect(requests: list[Request], deadline: float) -> None:
    """Wait for each request's answer until ``deadline``."""
    for r in requests:
        try:
            resp = r.ticket.result(max(0.0, deadline - time.monotonic()))
        except TimeoutError:
            continue
        r.response = resp
        r.done = getattr(resp, "served_at", None) or time.monotonic()


def closed_loop(front, dep, traffic: Traffic, clients: int, t0: float,
                seconds: float, points: dict) -> list[Request]:
    """``clients`` clients, each submitting a fresh request as soon as its
    last one is answered, until the window closes."""
    from repro.service.requests import RequestState
    done: queue.Queue = queue.Queue()
    out: list[Request] = []
    t_end = t0 + seconds
    stream = traffic.stream(clients)

    def submit():
        try:
            spec, recal = next(stream)
        except StopIteration:
            raise RuntimeError("the traffic's request stream ran out inside "
                               "the window") from None
        r = Request(spec=spec, due=time.monotonic(),
                    points=points[spec["h"], spec["w"]], recal=recal)
        r.submitted = r.due
        tech = None if recal is None else dep.recalibrated(*recal)

        def on_event(ev, r=r):
            if ev.kind in (RequestState.SERVED.value,
                           RequestState.SHEDDED.value):
                r.response = ev.response
                r.done = (getattr(ev.response, "served_at", None)
                          or time.monotonic())
                done.put(r)

        r.ticket = front.submit(dep.request(spec, tech), on_event=on_event)
        out.append(r)

    for _ in range(clients):
        submit()
    while True:
        left = t_end - time.monotonic()
        if left <= 0:
            break
        try:
            done.get(timeout=left)
        except queue.Empty:
            break
        if time.monotonic() < t_end:
            submit()
    return out


# ---------------------------------------------------------------------------
# The comparison with the reference
# ---------------------------------------------------------------------------


def judge(dep_cfg: dict, run: Run, seed: int) -> compare.Comparison:
    """Served answers of the window against the plain reference."""
    from reference import lattice as L
    from reference import macro as RM
    from reference.tech import recalibrated
    import traffic as T

    base = getattr(RM, dep_cfg["tech"])()

    def tech_of(r: Request):
        return base if r.recal is None else recalibrated(base, *r.recal)

    lat = dep_cfg["lattice"]
    lattice = L.Lattice(int(lat["precision_modes"]),
                        bool(lat["approx_cells"]))
    resolution = int(dep_cfg["request"]["resolution"])
    served = [r for r in run.requests if r.served
              and r.due < run.t_end]
    gen = T.rng(seed, 99)
    cmp = compare.Comparison()
    if dep_cfg["request"]["kind"] == "search":
        pick = gen.permutation(len(served))[:SEARCH_SAMPLE]
        memo: dict = {}
        for i in sorted(int(k) for k in pick):
            r = served[i]
            key = (tuple(sorted(r.spec.items())), r.recal)
            if key not in memo:
                memo[key] = L.search(RM.MacroSpec(**r.spec), tech_of(r),
                                     resolution)
            cmp.add_answer(f"search {r.spec}", r.response.result, memo[key])
        return cmp
    # sweeps: every served point against its own roll-up, and whole sweeps
    for r in served:
        spec, tech = RM.MacroSpec(**r.spec), tech_of(r)
        for p in r.response.result.frontier + r.response.result.explored:
            cmp.add_point(p, RM.rollup(compare.reference_design(
                p.design, spec), tech))
    pick = gen.permutation(len(served))[:SWEEP_SAMPLE]
    for i in sorted(int(k) for k in pick):
        r = served[i]
        cmp.add_answer(f"sweep {r.spec} {r.recal or ''}", r.response.result,
                       L.sweep(RM.MacroSpec(**r.spec), tech_of(r), lattice,
                               workers=REFERENCE_WORKERS))
    return cmp


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


def run(argv=None, *, require_chip: bool = True, root: Path = ROOT) -> int:
    args = parse(argv)
    bench = discovery.load_benchmark(root)
    wl = discovery.workload(bench, args.workload)
    cfg = discovery.config(bench, root, wl["config"])
    trf = discovery.traffic(wl["traffic"])
    section = "per_layer" if args.trace else "end_to_end"
    wanted = discovery.metrics(bench, section, wl["name"])
    readers = {m["name"]: discovery.reader(m["name"]) for m in wanted}
    chips = int(wl["chips"])

    sys.path.insert(0, str(Path(root) / "src"))
    import jax
    if require_chip:
        check_device(chips)
    from repro import obs
    from repro.runtime import enable_compile_cache
    import sut
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if args.fault:
        import faults
        faults.apply(args.fault)

    dep = sut.Deployment(cfg)
    traffic = Traffic(cfg, trf, args.seed)
    points = lattice_points(cfg, traffic.families)
    fe = trf["frontend"]
    max_batch, lane_counts = batching(traffic, chips)

    # -- set-up: warm the cell's programs, serve the first requests ----------
    marks = [("start-up", time.monotonic())]
    for k in range(len(traffic.families)):
        dep.warm_engine(traffic.warm_specs(max(lane_counts), k), lane_counts,
                        mixed=len(traffic.families) > 1)
    marks.append(("roll-up programs", time.monotonic()))
    buckets = dep.warm_sharded_mask(max(points.values()))
    marks.append(("sharded mask", time.monotonic()))
    store = tempfile.mkdtemp(prefix="bench-artifacts-")
    service = dep.service(store)
    front = dep.frontend(service, fe, max_batch)
    if traffic.loop == "open":
        warm = traffic.warm + traffic.pool
        front.serve([dep.request(s) for s in warm], timeout=600)
    elif traffic.recal is not None:
        pool, beside = traffic.recal_specs()
        front.serve([dep.request(s) for s in pool + [beside]], timeout=600)
        tech = dep.recalibrated(traffic.recal["field"],
                                traffic.recal["warm_factor"])
        front.serve([dep.request(beside, tech)], timeout=600)
    marks.append(("first requests and pool", time.monotonic()))
    log("set-up: " + ", ".join(
        f"{name} {t - prev:.3f} s" for (name, t), (_, prev)
        in zip(marks, [("", T_START)] + marks[:-1])))
    arrivals = (traffic.schedule(args.seconds)
                if traffic.loop == "open" else None)
    compiles = sut.compile_counter()
    stats0 = service.stats.as_dict()

    # -- the window ------------------------------------------------------------
    trace_dir = None
    if args.trace:
        obs.configure(enabled=True)
        obs.tracer.clear()
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(tracefile.SYNC):
            sync_mono = time.monotonic()
    t0 = time.monotonic()
    setup_s = t0 - T_START
    lowered0, compiled0 = compiles["lowered"], compiles["compiled"]
    if traffic.loop == "open":
        requests = open_loop(front, dep, arrivals, t0, args.seconds, points)
    else:
        requests = closed_loop(front, dep, traffic,
                               traffic.clients(chips), t0, args.seconds,
                               points)
    t_end = t0 + args.seconds
    if args.trace:
        jax.profiler.stop_trace()
    in_window = (compiles["lowered"] - lowered0,
                 compiles["compiled"] - compiled0)
    gave_up = t_end + LATE_WAIT_S
    if traffic.loop == "open":
        collect(requests, gave_up)
    fstats = front.stats.as_dict()
    front.close(drain=False)
    collect([r for r in requests if r.response is None], time.monotonic())
    sstats = service.stats.as_dict()
    spans = obs.tracer.drain() if args.trace else []
    obs.configure(enabled=False)
    device = sut.device_info(chips)
    lateness = [r.submitted - r.due for r in requests]
    log(f"window: {len(requests)} requests, generator late by at most "
        f"{max(lateness, default=0.0):.6f} s (mean "
        f"{sum(lateness) / max(1, len(lateness)):.6f} s)")
    log(f"frontend: {fstats}; service: {sstats}")
    log(f"service in the window: "
        f"{ {k: v - stats0[k] for k, v in sstats.items()} }; "
        f"{sum(r.served for r in requests)} of its requests served")
    if traffic.loop == "open":
        due = latency_halves(requests, t0, t_end, gave_up)
        log(f"latency p50 of the window's first / second half: {due}")
    log(f"compiles in window: {in_window[0]} programs lowered, "
        f"{in_window[1]} compiled (set-up warmed lane counts "
        f"{lane_counts[0]}..{lane_counts[-1]}, mask buckets {buckets})")

    result_run = Run(t0=t0, t_end=t_end, setup_s=setup_s, gave_up=gave_up,
                     requests=requests, spans=spans)
    executes = result_run.window_spans("engine.execute")
    if executes:
        log(f"engine in the window: {len(executes)} passes, "
            f"{result_run.lanes()} lanes, at most "
            f"{max(int(s.tags.get('n_groups', 0)) for s in executes)} "
            "groups in a pass")
    breakdown = None
    if args.trace:
        dig = tracefile.digest(tracefile.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        if args.out:
            Path(args.out).mkdir(parents=True, exist_ok=True)
            Path(args.out, f"{wl['name']}.{args.seed}.digest.json"
                 ).write_text(json.dumps(dig))
        if dig["devices"]:
            result_run.trace = dig
            result_run.trace_offset_ns = (tracefile.sync_ns(dig)
                                          - sync_mono * 1e9)
            lo, hi = result_run.trace_window()
            busy = tracefile.busy_ns(dig, lo, hi)
            device["busy_s"] = sum(busy.values()) / len(busy) / 1e9
            device["window_s"] = (hi - lo) / 1e9
            breakdown = tracefile.breakdown(
                dig, lo, hi, result_run.host_spans_on_trace())

    metrics = {}
    for m in wanted:
        value = readers[m["name"]](result_run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    attempted = (len(result_run.due_in_window()) if traffic.loop == "open"
                 else len([r for r in requests if r.done is not None
                           and r.done <= t_end]))
    failed = (sum(r.failed for r in result_run.due_in_window())
              if traffic.loop == "open"
              else sum(r.failed for r in requests
                       if r.done is not None and r.done <= t_end))

    # -- the comparison, after the window and the memory reading -------------
    del front, service
    shutil.rmtree(store, ignore_errors=True)
    t_ref = time.monotonic()
    log(f"after the window: {t_ref - t_end:.3f} s to collect answers, "
        "close the frontend and read the trace")
    cmp = judge(cfg, result_run, args.seed)
    lost = sum(r.response is None for r in result_run.due_in_window()) \
        if traffic.loop == "open" else 0
    checks = {"lost": {"value": lost, "limit": 0}, **cmp.checks()}
    correct = cmp.correct() and lost == 0
    log(f"reference: {cmp.answers} answers, {cmp.points} points compared "
        f"in {time.monotonic() - t_ref:.1f} s; worst field "
        f"{cmp.worst_field or '-'}")
    for note in cmp.notes:
        log(f"reference: {note}")
    missing = [m["name"] for m in wanted if m["name"] not in metrics
               and section == "end_to_end"]
    if missing:
        log(f"no reading for {missing}: the window completed nothing "
            "to measure")
        return 1
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


def main() -> int:
    try:
        return run(sys.argv[1:])
    except Refused as e:
        log(f"harness: {e}; no result")
        return 2


if __name__ == "__main__":
    sys.exit(main())
