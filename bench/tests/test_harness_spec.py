"""BENCHMARK.json resolves, name by name, to the benchmark's files."""

from __future__ import annotations

import json

import pytest

from harness import spec

BENCH = spec.load_benchmark()


@pytest.mark.parametrize("cell", [c["name"] for c in BENCH["workloads"]])
def test_every_cell_resolves_to_its_files(cell):
    c, cfg, mix = spec.resolve(BENCH, cell)
    assert cfg["name"] == c["config"]
    assert spec.kind_path(mix["kind"]).exists()
    e2e = {m["name"] for m in spec.metrics_of(BENCH, cell, "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = spec.metrics_of(BENCH, cell, "per_layer")
    assert layer
    for m in layer:
        assert m["moves"] in e2e


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    assert spec.metric_path(metric).exists()
    assert callable(spec.reader(metric))
    assert spec.reader(metric)({}) is None  # nothing to read: no number


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_every_configuration_file_states_its_source_and_cuts(config):
    with open(spec.ROOT / config["file"]) as f:
        cfg = json.load(f)
    assert cfg["source"] == config["source"]
    assert cfg["reduced"] == config["reduced"]
    for key in ("swap_interval_sweeps", "beta_spacing", "slots_per_chip"):
        assert key in cfg["assumed"]
    assert cfg["model"]["spins_per_layer"] * cfg["model"]["num_layers"] == 24576


def test_names_are_unique_and_metric_cells_exist():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        for cell in m.get("workloads", []):
            assert cell in {c["name"] for c in BENCH["workloads"]}
    assert sum(c["chips"] == 4 for c in BENCH["workloads"]) <= 1
    assert (spec.BENCH_DIR / "run.py").exists()
