"""Shared helpers of the benchmark's CPU tests: the harness's own modules
on the path, and tiny variants of the cells (a 6 x 8 lattice, the jnp
backend, a few slots) that drive the same harness code as the chip runs."""

from __future__ import annotations

import copy
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
SRC = BENCH.parent / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

from harness import cell, spec  # noqa: E402

LARGE_SEED = 2**31 + 12345


#: The test's cells: (configuration file, traffic file, changes to the mix).
CELLS = {
    "ladders": ("aqua-pt", "ladders", {}),
    "anneal-backlog": ("aqua-multi", "anneal-backlog", {}),
    "anneal-open": ("aqua-multi", "anneal-backlog", {"loop": "open", "rate_per_s": 40.0}),
}


def tiny(name: str):
    """(cell, configuration, mix) of a test cell cut to a CPU test's size."""
    config, traffic, changes = CELLS[name]
    cfg = copy.deepcopy(spec._read(spec.config_path(config)))
    mix = dict(spec._read(spec.traffic_path(traffic)), **changes)
    cfg["model"].update(spins_per_layer=6, num_layers=8)
    cfg["serve"]["backend"] = "jnp"
    cfg["slots_per_chip"] = 8
    if cfg["instances"] > 1:
        cfg["instances"] = 8
    if mix["kind"] == "pt_ladders":
        mix.update(ladders_per_chip=2, rungs=4)
    else:
        # Samples as large as the tiny pool, so every job is compared on any seed.
        mix.update(budget_min=64, budget_max=128, users=4, warmup_sweeps=128,
                   sample_jobs=1000, sample_active=cfg["slots_per_chip"])
        if mix.get("rate_per_s"):
            mix.update(gap_block=16)
    return dict(name=name, config=config, traffic=traffic, chips=1), cfg, mix


_COUNTER = None


def run_cell(c: dict, cfg: dict, mix: dict, seed: int = LARGE_SEED, seconds: float = 0.6) -> dict:
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = cell.CompileCounter()
    return cell.run(c, cfg, mix, seed, seconds, False, time.perf_counter(), _COUNTER)


def run_tiny(name: str, seed: int = LARGE_SEED, seconds: float = 0.6) -> dict:
    return run_cell(*tiny(name), seed, seconds)


@pytest.fixture
def tiny_run():
    return run_tiny


@pytest.fixture
def cell_run():
    return run_cell


@pytest.fixture
def tiny_cell():
    return tiny
