"""Finds the benchmark's parts by name, from files alone.

* ``BENCHMARK.json`` at the checkout's root: cells, configurations and
  metrics;
* ``bench/configs/<config>.json``: a configuration's sizes;
* ``bench/workloads/<cell>.json``: a cell's driver, traffic parameters
  and why;
* ``bench/drivers/<driver>.py``: set-up, warm-up, one request and the
  output check of one kind of traffic;
* ``bench/metrics/<metric>.py``: a ``read(ctx)`` that returns the
  metric's value, or ``None`` where it finds nothing to read.

Adding any of them is adding a file: nothing here lists them.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
from types import ModuleType

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def load_json(path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def benchmark(root=ROOT) -> dict:
    """The parsed ``BENCHMARK.json``."""
    return load_json(pathlib.Path(root) / "BENCHMARK.json")


def _load_module(path: pathlib.Path, tag: str) -> ModuleType:
    if not path.is_file():
        raise KeyError(f"no {tag} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{tag}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config(name: str, bench_dir=BENCH_DIR) -> dict:
    return load_json(pathlib.Path(bench_dir) / "configs" / f"{name}.json")


def workload(name: str, bench_dir=BENCH_DIR) -> dict:
    return load_json(pathlib.Path(bench_dir) / "workloads" / f"{name}.json")


def driver(name: str, bench_dir=BENCH_DIR) -> ModuleType:
    return _load_module(pathlib.Path(bench_dir) / "drivers" / f"{name}.py",
                        "driver")


def metric(name: str, bench_dir=BENCH_DIR) -> ModuleType:
    return _load_module(pathlib.Path(bench_dir) / "metrics" / f"{name}.py",
                        "metric")


def cell_metrics(bench: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries a cell reports: those
    that list it under ``workloads``, or that list no cells at all."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def resolve_cell(bench: dict, cell: str, bench_dir=BENCH_DIR) -> dict:
    """Everything one cell needs: its ``BENCHMARK.json`` entry, its
    workload file, its configuration and its configuration entry."""
    entry = next((w for w in bench["workloads"] if w["name"] == cell), None)
    if entry is None:
        raise KeyError(f"unknown workload {cell!r}; known: "
                       f"{[w['name'] for w in bench['workloads']]}")
    wl = workload(cell, bench_dir)
    if wl["config"] != entry["config"] or wl["traffic"] != entry["traffic"]:
        raise ValueError(f"{cell}: workload file and BENCHMARK.json disagree "
                         "on config or traffic")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    return dict(entry=entry, workload=wl,
                config=config(wl["config"], bench_dir),
                config_entry=cfg_entry)
