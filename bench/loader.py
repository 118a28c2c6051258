"""Finds a cell's configuration, traffic mix and metric readers by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own:

* ``bench/configs/<config>.json``
* ``bench/traffic/<traffic>.json``
* ``bench/metrics/<metric>.py``, which defines ``read(ctx)``

so a new cell needs new files and new entries in ``BENCHMARK.json``,
and no edit of a file that is already there.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as fh:
        return json.load(fh)


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    known = ", ".join(w["name"] for w in bench["workloads"])
    raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")


def _json(kind: str, name: str, bench_dir: Path) -> dict:
    path = bench_dir / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"{kind[:-1] if kind.endswith('s') else kind}"
                                f" {name!r}: no file {path}")
    with open(path) as fh:
        return json.load(fh)


def load_config(name: str, bench_dir: Path = BENCH) -> dict:
    return _json("configs", name, bench_dir)


def load_traffic(name: str, bench_dir: Path = BENCH) -> dict:
    return _json("traffic", name, bench_dir)


def metrics_of(bench: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries that ``cell``
    reports: those that list it, and those with no list at all."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def load_reader(name: str, bench_dir: Path = BENCH):
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"metric {name!r}: no reader {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
