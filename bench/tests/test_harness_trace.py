"""The trace reduction, checked on a trace recorded on a TPU v5e
(``bench/trace_sample/``, the normalized record of a traced
``aqua-pt.ladders`` window) against a plain sweep over the events' edges."""

from __future__ import annotations

from pathlib import Path

import numpy as np
import pytest

from harness import tracing

SAMPLE = Path(__file__).resolve().parents[1] / "trace_sample" / "aqua-pt.ladders.json.gz"


@pytest.fixture(scope="module")
def record():
    return tracing.load(str(SAMPLE))


def _busy_ns(record, events) -> int:
    """Nanoseconds in which at least one event runs: a sweep over the
    sorted edges, counting open events."""
    w0, w1 = record["window"]
    edges = []
    for _, s, d, *_ in events:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            edges += [(a, 1), (b, -1)]
    edges.sort(key=lambda e: (e[0], -e[1]))
    busy, depth, since = 0, 0, None
    for t, step in edges:
        if depth == 0 and step == 1:
            since = t
        depth += step
        if depth == 0:
            busy += t - since
    return busy


def test_busy_union_and_gaps_fill_the_window(record):
    r = tracing.reduce(record)
    w0, w1 = record["window"]
    assert r["window_s"] == pytest.approx((w1 - w0) / 1e9)
    busy = np.mean([_busy_ns(record, evs) / 1e9 for evs in record["devices"].values()])
    assert r["busy_s"] == pytest.approx(busy, rel=1e-12)
    assert 0 < r["busy_s"] <= r["window_s"]
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle + r["busy_s"] == pytest.approx(r["window_s"], rel=1e-6)
    labels = {label for _, label in tracing.GAP_LABELS} | {"client: between steps"}
    assert {k for k, _ in r["idle_gaps"]} <= labels


def test_kernel_time_is_the_sweep_kernels_events(record):
    r = tracing.reduce(record)
    w0, w1 = record["window"]
    total = sum(min(s + d, w1) - max(s, w0) for evs in record["devices"].values()
                for ev in evs for _, s, d, *_ in [ev]
                if tracing.is_kernel(ev) and min(s + d, w1) > max(s, w0))
    assert r["kernel_s"] == pytest.approx(total / 1e9)
    assert r["kernel_s"] > 0.5 * r["busy_s"]
    top = r["device_ops"][0][0]
    assert tracing.is_kernel([top, 0, 0, ""]) or any(
        tracing.is_kernel(ev) for evs in record["devices"].values() for ev in evs if ev[0] == top)


def test_counters_and_spans_came_with_the_trace(record):
    r = tracing.reduce(record)
    assert r["counters"]["busy_slot_sweeps"] > 0
    assert r["spans"]["sched.step"]
