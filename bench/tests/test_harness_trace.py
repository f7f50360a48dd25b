"""The trace reduction, checked on traces recorded on a TPU v5e
(``bench/trace_sample/``, normalized records of traced windows) against
a plain sweep over the events' edges."""

from __future__ import annotations

from pathlib import Path

import numpy as np
import pytest

from harness import tracing

SAMPLES = Path(__file__).resolve().parents[1] / "trace_sample"
SAMPLE = SAMPLES / "aqua-pt.ladders.json.gz"


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
    labels = {n for n, _, _ in record["host"]} | {tracing.WINDOW}
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


def _sweep_ns(record, ops, spans) -> int:
    """Nanoseconds of the window in which no op runs and some span does: a
    sweep over the sorted edges of both, counting what is open."""
    w0, w1 = record["window"]
    edges = []
    for _, s, d, *_ in ops:
        edges += [(s, 0, 1), (s + d, 0, -1)]
    for _, s, d in spans:
        edges += [(s, 1, 1), (s + d, 1, -1)]
    edges.sort()
    depth, out, last = [0, 0], 0, None
    for t, which, step in edges:
        if last is not None and depth[0] == 0 and depth[1] > 0:
            out += max(0, min(t, w1) - max(last, w0))
        depth[which] += step
        last = t
    return out


def _innermost(spans, t):
    covering = [(s, -d, n) for n, s, d in spans if s <= t <= s + d]
    return max(covering)[2] if covering else None


@pytest.fixture(scope="module", params=sorted(p.name for p in SAMPLES.glob("*.json.gz")))
def sample(request):
    return tracing.load(str(SAMPLES / request.param))


def test_every_program_span_gets_its_durations_and_the_idle_under_it(sample):
    r = tracing.reduce(sample)
    w0, w1 = sample["window"]
    program = {n for n, _, _ in sample["host"] if not n.startswith(tracing.BENCH_PREFIX)}
    assert program and program <= set(r["spans"]) and program <= set(r["idle_under"])
    for name in program:
        spans = [h for h in sample["host"] if h[0] == name]
        inside = [d for _, s, d in spans if w0 <= s and s + d <= w1]
        assert r["spans"][name] == [d / 1e9 for d in inside]
        idle = np.mean([_sweep_ns(sample, evs, spans) for evs in sample["devices"].values()])
        assert r["idle_under"][name] == pytest.approx(idle / 1e9, rel=1e-9, abs=1e-12)
        assert 0 <= r["idle_under"][name] <= r["window_s"] - r["busy_s"] + 1e-9


def test_each_idle_gap_is_filed_under_the_innermost_program_span(sample):
    r = tracing.reduce(sample)
    w0, w1 = sample["window"]
    program = [h for h in sample["host"] if not h[0].startswith(tracing.BENCH_PREFIX)]
    bench = [h for h in sample["host"] if h[0].startswith(tracing.BENCH_PREFIX)]
    want = {}
    for evs in sample["devices"].values():
        busy = []
        for _, s, d, *_ in sorted(evs, key=lambda e: e[1]):
            a, b = max(s, w0), min(s + d, w1)
            if b <= a:
                continue
            if busy and a <= busy[-1][1]:
                busy[-1][1] = max(busy[-1][1], b)
            else:
                busy.append([a, b])
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                mid = (s + e) / 2
                label = _innermost(program, mid) or _innermost(bench, mid) or tracing.WINDOW
                want[label] = want.get(label, 0) + (e - s) / 1e9 / len(sample["devices"])
    top = sorted(want.items(), key=lambda kv: -kv[1])[:10]
    assert [k for k, _ in r["idle_gaps"]] == [k for k, _ in top]
    assert [v for _, v in r["idle_gaps"]] == pytest.approx([v for _, v in top], rel=1e-9)
    assert any(k in {n for n, _, _ in program} for k, _ in r["idle_gaps"])


def test_the_programs_counters_come_as_window_deltas(sample):
    c = tracing.reduce(sample)["counters"]
    assert c["launches"] > 0 and c["serve.launches"] == c["launches"]
    assert c["serve.busy_slot_sweeps"] == c["busy_slot_sweeps"] > 0
    assert c["serve.total_slot_sweeps"] == c["total_slot_sweeps"]


def test_a_traced_window_keeps_the_programs_spans_and_counters(tiny_cell, tmp_path):
    """On the CPU (no device plane): what `Window` keeps from the host plane
    is every span the program opened in the window, and the counters are
    the window's deltas."""
    from harness import cell, models, spec

    c, cfg, mix = tiny_cell("ladders")
    served = cell.Served(cfg, 1, models.instances(cfg, 5), spec.kind(mix["kind"]))
    loop = cell.Loop(served, mix, 1, 5)
    loop.start()
    for _ in range(4):
        loop.tick()
    window = tracing.Window(str(tmp_path), served.server)
    window.start()
    for _ in range(6):
        loop.tick()
    window.stop()
    names = [n for n, _, _ in window.record["host"]]
    for name in ("bench.step", "sched.step", "sched.admit", "engine.dispatch", "engine.wait",
                 "pt.hook", "pt.swap"):
        assert names.count(name) == 6, name
    assert "engine.launch" not in names  # a complete event of the ring, not an annotation
    c = window.record["counters"]
    assert c["serve.launches"] == c["launches"] == 6
    assert c["pt.swap_local"] == 6 and c["serve.busy_slot_sweeps"] == c["busy_slot_sweeps"] == 6 * 8 * 8
