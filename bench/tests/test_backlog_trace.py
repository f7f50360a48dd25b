"""The traced ``aqua-multi.anneal-backlog`` window recorded on a TPU v5e
(``bench/trace_sample/aqua-multi.anneal-backlog.json.gz``): it reduces,
its metrics read, and its host plane holds the program's own spans of
admission, the anneal hook and retirement, each inside a scheduling
step.  Those spans are profiler annotations the program writes
(`Telemetry.span`); the normalized record keeps them, and the step's
own span, from the trace's host plane on the profiler's clock."""

from __future__ import annotations

from pathlib import Path

import pytest

from harness import peaks, spec, tracing

SAMPLE = Path(__file__).resolve().parents[1] / "trace_sample" / "aqua-multi.anneal-backlog.json.gz"
BENCH = spec.load_benchmark()
# The spans of one scheduling step of this cell, below ``sched.step``.
STEP_SPANS = ("anneal.hook", "engine.set_betas", "job.finalize", "engine.extract",
              "job.init", "engine.splice", "engine.dispatch", "engine.wait")


@pytest.fixture(scope="module")
def record():
    return tracing.load(str(SAMPLE))


def _inside(rec, name) -> list:
    """For each ``name`` span of the window, whether a ``sched.step``
    holds it."""
    w0, w1 = rec["window"]
    outer = [(s, s + d) for n, s, d in rec["host"] if n == "sched.step"]
    return [any(a <= s and s + d <= b for a, b in outer)
            for n, s, d in rec["host"] if n == name and w0 <= s and s + d <= w1]


@pytest.mark.parametrize("name", STEP_SPANS)
def test_each_program_span_of_a_step_lies_inside_a_scheduling_step(record, name):
    held = _inside(record, name)
    assert held and all(held)


def test_the_backlog_window_reduces_and_the_trace_readers_read_it(record):
    r = tracing.reduce(record)
    assert r["devices"] == 1 and 0 < r["kernel_s"] < r["busy_s"] < r["window_s"]
    ctx = dict(trace=r, peaks=peaks.peaks_for("TPU v5 lite"), spins_per_replica=24576)
    assert 0 < spec.reader("device_idle_share.pt")(ctx) < 100
    assert spec.reader("sched_admit_ms")(ctx) > 0
    assert not [n for n, _, _ in record["host"] if n.startswith("pt.")]  # no ladder here


@pytest.mark.parametrize("metric", [m for m in BENCH["per_layer"] if "workloads" in m],
                         ids=lambda m: m["name"])
def test_a_per_layer_metric_names_cells_that_report_what_it_moves(metric):
    cells = {c["name"] for c in BENCH["workloads"]}
    assert metric["workloads"] and set(metric["workloads"]) <= cells
    for cell in metric["workloads"]:
        assert metric["moves"] in {m["name"] for m in spec.metrics_of(BENCH, cell, "end_to_end")}
        assert metric in spec.metrics_of(BENCH, cell, "per_layer")
