"""Benchmark orchestrator: one module per paper table/figure + kernels, DSE
and the roofline reader.  Prints ``name,us_per_call,derived`` CSV and, with
``--json <path>``, writes machine-readable rows for CI perf artifacts."""

from __future__ import annotations

import argparse
import json
import platform
import sys
import time
import traceback

from repro.runtime import enable_compile_cache

from . import (bench_csa, bench_dse, bench_fig7_energy, bench_fig8_pareto,
               bench_fig9_shmoo, bench_frontend, bench_kernels,
               bench_lattice, bench_multispec, bench_obs, bench_pareto,
               bench_roofline, bench_service, bench_shardspec,
               bench_table1_features, bench_table2_sota)
from .common import emit, rows_to_dicts

MODULES = [
    ("fig7", bench_fig7_energy),
    ("fig8", bench_fig8_pareto),
    ("fig9", bench_fig9_shmoo),
    ("table1", bench_table1_features),
    ("table2", bench_table2_sota),
    ("csa", bench_csa),
    ("kernels", bench_kernels),
    ("dse", bench_dse),
    ("multispec", bench_multispec),
    ("shardspec", bench_shardspec),
    ("pareto", bench_pareto),
    ("lattice", bench_lattice),
    ("service", bench_service),
    ("frontend", bench_frontend),
    ("obs", bench_obs),
    ("roofline", bench_roofline),
]


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default=None,
                    help="comma-separated module subset (e.g. fig8,dse) — "
                         "used by the CI smoke job")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as a JSON perf artifact")
    args = ap.parse_args(argv)
    enable_compile_cache()

    selected = MODULES
    if args.only:
        wanted = [s.strip() for s in args.only.split(",") if s.strip()]
        unknown = [w for w in wanted if w not in {n for n, _ in MODULES}]
        if unknown:
            ap.error(f"unknown benchmark module(s): {unknown}; "
                     f"available: {[n for n, _ in MODULES]}")
        selected = [(n, m) for n, m in MODULES if n in wanted]

    print("name,us_per_call,derived")
    failed = []
    all_rows: list[dict] = []
    for name, mod in selected:
        try:
            rows = mod.run()
            emit(rows)
            all_rows.extend(rows_to_dicts(name, rows))
        except Exception:
            failed.append(name)
            traceback.print_exc()

    if args.json:
        artifact = {
            "schema": "syndcim-bench/v1",
            "unix_time": time.time(),
            "platform": platform.platform(),
            "python": platform.python_version(),
            "modules": [n for n, _ in selected],
            "failed": failed,
            "rows": all_rows,
        }
        with open(args.json, "w") as f:
            json.dump(artifact, f, indent=2)
        print(f"# wrote {len(all_rows)} rows to {args.json}", file=sys.stderr)

    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
