"""Finds a cell's files by the names in BENCHMARK.json.

``configs/<config>.json``, ``traffic/<traffic>.json``,
``kinds/<kind>.py`` (the traffic file's ``kind``) and
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


def kind_path(name: str) -> Path:
    return BENCH_DIR / "kinds" / f"{name}.py"


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


def _load(path: Path, prefix: str):
    spec = importlib.util.spec_from_file_location(f"{prefix}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric_name: str):
    """The ``read(ctx)`` function of a per-layer metric's file."""
    return _load(metric_path(metric_name), "bench_metric").read


_KINDS = {}  # path -> module: one module object, and one set of jitted replays


def kind(name: str):
    """The module of a job kind, which a mix names by its ``kind``.  It gives:

    * ``specs(mix, chips, seed, instances)``: the job specs, a list the
      loop submits at once (a closed, fixed set) or a stream whose
      ``next()`` gives the next spec (backlogs and open loops);
    * ``make_job(served, spec)``: the program's job for a spec;
    * ``outputs(served, loop, pool, mix, rng)``: what the window produced,
      read at the close (``pool``: the jobs due in the window that retired);
    * ``numbers(out, outputs, lat, insts, ftype)``: adds the outputs' gaps
      to its replay on the shared reference to ``out`` (`check.numbers`);
    * ``control(lat, insts, mix, seed, ftype, sweeps)``: the reference in
      ``ftype`` in the shape ``outputs`` has (``bench/control.py``);
    * optionally ``LIMITS``: numbers of its own, ``{name: (limit, reason)}``.
    """
    path = kind_path(name)
    if not path.exists():
        raise KeyError(f"no job kind {name!r}: {path} does not exist")
    if path not in _KINDS:
        _KINDS[path] = _load(path, "bench_kind")
    return _KINDS[path]
