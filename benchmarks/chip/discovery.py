"""Finds a cell's pieces by name, from ``BENCHMARK.json`` at the checkout's
root: the workload entry, its configuration file (the path the entry names),
its traffic file ``traffic/<name>.json`` and one reader module
``metrics/<name>.py`` per metric.  Adding a configuration, a traffic mix or
a metric is adding its file and its entry; no code here names any of them.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


def load_benchmark(root: Path) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _named(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    return _named(bench["workloads"], name, "workload")


def config(bench: dict, root: Path, name: str) -> dict:
    entry = _named(bench["configs"], name, "config")
    return json.loads((Path(root) / entry["file"]).read_text())


def traffic(name: str, here: Path = HERE) -> dict:
    return json.loads((here / "traffic" / f"{name}.json").read_text())


def metrics(bench: dict, section: str, cell: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports: those that
    list it under ``workloads``, and those with no such list."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def reader(name: str, here: Path = HERE):
    """The ``read(run)`` function of ``metrics/<name>.py``."""
    path = here / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
