"""Find the benchmark's parts by the names BENCHMARK.json gives them.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own, found by name:

  bench/configs/<config>.json    sizes of a model, as run
  bench/traffic/<traffic>.json   parameters of a traffic mix
  bench/cells/<workload>.json    what one cell fixes: slots, rate, limits
  bench/metrics/<metric>.py      a reader with ``read(ctx) -> float | None``

so a later change adds a configuration, a mix, a cell or a metric by
adding files and entries alone.  ``root`` is the directory that holds
BENCHMARK.json and ``bench/``; tests point it at a temporary copy.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCH_DIR = "bench"


def _load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return _load_json(root / "BENCHMARK.json")


def _entry(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def config(name: str, root: pathlib.Path = ROOT) -> dict:
    return _load_json(root / BENCH_DIR / "configs" / f"{name}.json")


def traffic(name: str, root: pathlib.Path = ROOT) -> dict:
    return _load_json(root / BENCH_DIR / "traffic" / f"{name}.json")


def cell_params(workload: str, root: pathlib.Path = ROOT) -> dict:
    return _load_json(root / BENCH_DIR / "cells" / f"{workload}.json")


def metric_reader(name: str, root: pathlib.Path = ROOT):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = root / BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell(workload: str, root: pathlib.Path = ROOT) -> dict:
    """Everything one run of ``workload`` needs, resolved by name: its
    BENCHMARK.json entry, configuration, traffic, cell parameters and the
    metrics it reports (end-to-end and per-layer, each a BENCHMARK.json
    entry)."""
    bm = benchmark(root)
    w = _entry(bm["workloads"], workload, "workload")

    def reports(m: dict) -> bool:
        return "workloads" not in m or workload in m["workloads"]

    return {
        "workload": w,
        "config": config(w["config"], root),
        "traffic": traffic(w["traffic"], root),
        "params": cell_params(workload, root),
        "end_to_end": [m for m in bm["end_to_end"] if reports(m)],
        "per_layer": [m for m in bm["per_layer"] if reports(m)],
    }
