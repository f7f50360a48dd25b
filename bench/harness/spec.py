"""Finds a cell's files by the names in BENCHMARK.json.

``configs/<config>.json``, ``traffic/<traffic>.json`` and
``metrics/<metric>.py`` (the metric's name up to its first ``.``) under
the benchmark's directory; nothing else names a cell."""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _read(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def config_path(name: str) -> Path:
    return BENCH_DIR / "configs" / f"{name}.json"


def traffic_path(name: str) -> Path:
    return BENCH_DIR / "traffic" / f"{name}.json"


def metric_path(name: str) -> Path:
    return BENCH_DIR / "metrics" / f"{name.split('.', 1)[0]}.py"


def resolve(bench: dict, workload: str) -> tuple[dict, dict, dict]:
    """(cell, configuration, traffic mix) of the named workload."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    return cell, _read(config_path(cell["config"])), _read(traffic_path(cell["traffic"]))


def metrics_of(bench: dict, workload: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` metrics the workload reports."""
    moved = {m["name"] for m in bench["end_to_end"]
             if workload in m.get("workloads", [workload])}
    out = []
    for m in bench[kind]:
        cells = m.get("workloads")
        if cells is not None:
            if workload in cells:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in moved:
            out.append(m)
    return out


def reader(metric_name: str):
    """The ``read(ctx)`` function of a per-layer metric's file."""
    path = metric_path(metric_name)
    spec = importlib.util.spec_from_file_location(f"bench_metric_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
