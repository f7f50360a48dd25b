"""Observability (repro.obs): telemetry, exporters, stream tap, skew.

The load-bearing guarantee (DESIGN.md §Observability): observation never
touches carries.  Telemetry-on runs are bit-identical to telemetry-off
runs on every rung/backend combination, sharded or not; the trace the
server emits is schema-valid Chrome trace-event JSON; the event ring is
bounded with visible drop accounting; and `stats()` reads the SAME
registry the exporters scrape, so their numbers cannot disagree.
"""

import json

import jax
import numpy as np
import pytest

from repro.core import ising
from repro.obs import (
    LaunchSkewMonitor,
    ObservableStream,
    Telemetry,
    validate_events,
)
from repro.obs.trace import REQUIRED_FIELDS
from repro.serve_mc import AnnealJob, PTJob, ProfilerError, SampleServer

MODEL = ising.random_layered_model(n=5, L=8, seed=1, beta=1.0)
MIXED = [(10, 9), (11, 7), (12, 5)]  # (seed, budget)


def _server(m=MODEL, **kw):
    kw.setdefault("rung", "a4")
    kw.setdefault("backend", "jnp")
    kw.setdefault("V", 4)
    kw.setdefault("slots", 4)
    kw.setdefault("chunk_sweeps", 4)
    return SampleServer(m, **kw)


def _mixed_jobs():
    jobs = [
        AnnealJob.constant(seed=s, sweeps=b, beta=1.0) for s, b in MIXED
    ]
    jobs.append(
        PTJob(seed=9, betas=np.linspace(0.5, 1.5, 2), num_rounds=3,
              sweeps_per_round=4)
    )
    return jobs


def _drain(srv):
    for j in _mixed_jobs():
        srv.submit(j)
    return sorted(srv.drain(), key=lambda r: r.jid)


# -----------------------------------------------------------------------------
# Telemetry primitives.
# -----------------------------------------------------------------------------


def test_counter_is_monotone():
    tel = Telemetry()
    c = tel.counter("x")
    c.add(3)
    c.add(0)
    assert c.value == 3
    with pytest.raises(ValueError):
        c.add(-1)


def test_labeled_series_are_distinct():
    tel = Telemetry()
    tel.counter("launches", chunk=4).add(2)
    tel.counter("launches", chunk=8).add(1)
    assert tel.value("launches", chunk=4) == 2
    assert tel.value("launches", chunk=8) == 1
    assert tel.value("launches") == 0  # the unlabeled series is its own
    series = {labels["chunk"]: v for labels, v in tel.series("launches")}
    assert series == {4: 2, 8: 1}


def test_histogram_snapshot_percentiles():
    tel = Telemetry()
    h = tel.histogram("lat")
    for v in range(1, 101):
        h.observe(float(v))
    snap = h.snapshot()
    assert snap["count"] == 100 and snap["min"] == 1.0 and snap["max"] == 100.0
    assert abs(snap["p50"] - 50.5) < 1.0
    assert snap["p95"] > 90.0


def test_event_ring_is_bounded_with_visible_drops():
    """A long run cannot grow the ring: only the most recent ``max_events``
    survive and the eviction count is exact, surfaced in the snapshot AND
    as a marker event in the exported trace."""
    tel = Telemetry(max_events=64)
    for i in range(1000):
        tel.instant("tick", i=i)
    assert tel.num_events == 64
    assert tel.dropped_events == 1000 - 64
    # the survivors are the MOST RECENT ones
    assert [ev["args"]["i"] for ev in tel.events()] == list(range(936, 1000))
    assert tel.metrics_snapshot()["events_dropped"] == 936
    trace = tel.chrome_trace()
    marker = [e for e in trace["traceEvents"]
              if e["name"] == "events_dropped_by_ring"]
    assert len(marker) == 1 and marker[0]["args"]["dropped"] == 936


def test_span_nesting_enforced():
    tel = Telemetry()
    with tel.span("outer"):
        with tel.span("inner"):
            tel.instant("tick")
    names = [(e["name"], e["ph"]) for e in tel.events()]
    assert names == [("outer", "B"), ("inner", "B"), ("tick", "i"),
                     ("inner", "E"), ("outer", "E")]
    validate_events(tel.events())


def test_disabled_telemetry_keeps_counting():
    """enabled=False silences events only: stats()/exporters still need
    the metrics, so counters keep counting."""
    tel = Telemetry(enabled=False)
    tel.counter("c").add(5)
    tel.instant("never")
    with tel.span("nor-this"):
        pass
    assert tel.num_events == 0
    assert tel.value("c") == 5


# -----------------------------------------------------------------------------
# The server's trace: schema-valid, with the advertised event taxonomy.
# -----------------------------------------------------------------------------


def test_server_trace_schema_and_taxonomy(tmp_path):
    srv = _server(policy="fair")
    _drain(srv)
    path = srv.telemetry.write_chrome_trace(tmp_path / "trace.json")
    trace = json.loads(open(path).read())
    events = trace["traceEvents"]
    assert trace["displayTimeUnit"] == "ms"
    validate_events(events)
    for ev in events:
        for field in REQUIRED_FIELDS:
            assert field in ev
    names = {e["name"] for e in events}
    # job lifecycle (async spans), engine launches (complete events),
    # scheduler phases (sync spans) and decisions (instants) all present
    assert {"job", "engine.launch", "sched.step", "sched.admit",
            "sched.plan"} <= names
    jobs = [e for e in events if e["name"] == "job"]
    assert {e["ph"] for e in jobs} == {"b", "n", "e"}
    begins = [e for e in jobs if e["ph"] == "b"]
    ends = [e for e in jobs if e["ph"] == "e"]
    assert len(begins) == len(ends) == 4  # every job opened and closed
    assert {e["args"]["kind"] for e in begins} == {"anneal", "pt"}
    # every admitted job reported its wait at admission
    admits = [e for e in jobs
              if e["ph"] == "n" and e["args"]["phase"] == "admit"]
    assert len(admits) == 4
    assert all("wait_s" in e["args"] for e in admits)
    launches = [e for e in events if e["name"] == "engine.launch"]
    assert len(launches) == srv.launches
    assert all(e["ph"] == "X" and e["dur"] > 0 for e in launches)
    # first launch of each chunk size is flagged as the compiling one
    first_by_chunk = {}
    for e in launches:
        first_by_chunk.setdefault(e["args"]["chunk"], e)
    assert all(e["args"]["compile"] for e in first_by_chunk.values())
    steady = [e for e in launches
              if e not in first_by_chunk.values()]
    assert all(not e["args"]["compile"] for e in steady)


def test_preemption_emits_park_and_resume(tmp_path):
    """The fair policy's checkpoint-preemption shows up in the trace as
    park (with reason) + resume instants on the evicted job's span."""
    srv = _server(slots=2, chunk_sweeps=2, policy="fair")
    low = AnnealJob.constant(seed=1, sweeps=40, beta=1.0, priority=0)
    srv.submit(low)
    srv.step()  # low is resident
    hi = [AnnealJob.constant(seed=s, sweeps=4, beta=1.0, priority=5)
          for s in (2, 3)]
    for j in hi:
        srv.submit(j)
    srv.drain()
    assert low.preemptions >= 1
    evs = [e for e in srv.telemetry.events()
           if e["name"] == "job" and e["ph"] == "n"
           and e.get("id") == str(low.jid)]
    phases = [e["args"]["phase"] for e in evs]
    assert "park" in phases and "resume" in phases
    park = next(e for e in evs if e["args"]["phase"] == "park")
    assert park["args"]["reason"] == "preempt"


# -----------------------------------------------------------------------------
# Bit-exactness: observation never changes results.
# -----------------------------------------------------------------------------


@pytest.mark.parametrize("rung", ["a4", "cb"])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_results_identical_with_telemetry_on_off(rung, backend):
    kw = dict(rung=rung, backend=backend)
    if backend == "pallas":
        # pallas needs L % V == 0; interpret mode keeps it CPU-runnable
        m = ising.random_layered_model(n=2, L=256, seed=4, beta=1.0)
        kw.update(m=m, V=128, interpret=True)
    off = _drain(_server(telemetry=False, **kw))
    on = _drain(_server(telemetry=True, **kw))
    tapped = _drain(_server(stream=ObservableStream(), **kw))
    assert len(off) == len(on) == len(tapped) == 4
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a.spins, b.spins)
        np.testing.assert_array_equal(a.energy, b.energy)
    for a, b in zip(off, tapped):
        np.testing.assert_array_equal(a.spins, b.spins)


@pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="sharded parity needs >= 4 devices "
    "(run with XLA_FLAGS=--xla_force_host_platform_device_count=4)",
)
def test_results_identical_with_telemetry_on_off_sharded():
    """D=4 mesh: the per-device ready-time probe and the skew monitor run
    on every launch — and must not move a single bit."""
    from repro.launch.mesh import make_slot_mesh

    mesh = make_slot_mesh(4)
    off = _drain(_server(telemetry=False, mesh=mesh))
    on_srv = _server(telemetry=True, mesh=mesh)
    on = _drain(on_srv)
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a.spins, b.spins)
        np.testing.assert_array_equal(a.energy, b.energy)
    # the probe actually ran: one per-device sample set per launch
    assert on_srv._skew is not None
    assert on_srv._skew.launches == on_srv.launches
    assert on_srv.stats()["telemetry"]["devices"] == 4


# -----------------------------------------------------------------------------
# stats() and the exporters read ONE registry.
# -----------------------------------------------------------------------------


def test_stats_and_exporters_agree():
    srv = _server()
    _drain(srv)
    st = srv.stats()
    tel = srv.telemetry
    snap = tel.metrics_snapshot()
    assert st["launches"] == tel.value("serve.launches") \
        == snap["counters"]["serve.launches"]
    assert st["busy_slot_sweeps"] == snap["counters"]["serve.busy_slot_sweeps"]
    assert st["total_slot_sweeps"] == snap["counters"]["serve.total_slot_sweeps"]
    assert st["sweeps_elapsed"] == snap["counters"]["serve.sweeps_elapsed"]
    assert st["preemptions"] == tel.value("serve.preemptions")
    assert sum(srv.launch_chunks.values()) == st["launches"]
    assert st["distinct_chunks"] == len(srv.launch_chunks)
    txt = tel.prometheus_text()
    assert f"repro_serve_launches {st['launches']}" in txt
    assert "# TYPE repro_serve_launches counter" in txt
    assert "# TYPE repro_serve_launch_s summary" in txt
    assert 'repro_serve_launches_by_chunk{chunk="4"}' in txt
    json.dumps(snap)  # snapshot must be JSON-clean


def test_stats_identical_with_telemetry_off():
    """Sweep accounting is metrics, not events: the full stats() dict
    (minus wall-clock noise) survives telemetry=False."""
    on = _server(telemetry=True, policy="fifo")
    off = _server(telemetry=False, policy="fifo")
    _drain(on)
    _drain(off)
    a, b = on.stats(), off.stats()
    for k in ("launches", "busy_slot_sweeps", "total_slot_sweeps",
              "sweeps_elapsed", "preemptions", "utilization",
              "distinct_chunks", "spin_flips"):
        assert a[k] == b[k], k
    assert b["telemetry"]["events_recorded"] == 0


# -----------------------------------------------------------------------------
# Per-chunk observable streaming.
# -----------------------------------------------------------------------------


def test_stream_traces_and_best_so_far():
    stream = ObservableStream()
    seen = []
    stream.subscribe(seen.append)
    srv = _server(stream=stream, policy="fifo")
    results = _drain(srv)
    assert stream.samples_taken == len(seen) > 0
    for r in results:
        tr = stream.trace(r.jid)
        assert tr, f"job {r.jid} never sampled"
        # job-local sweep clock is monotone along the trace and ends at
        # the job's full budget
        done = [s.sweeps_done for s in tr]
        assert done == sorted(done) and done[-1] == r.sweeps_done
        # the last sample IS the retirement state: hooks between the tap
        # and finalize rewrite betas only, never spins
        last = tr[-1]
        np.testing.assert_allclose(
            np.atleast_1d(np.asarray(r.energy, np.float64)), last.energy
        )
        best = stream.best(r.jid)
        assert best is not None
        assert best.energy <= float(np.min(last.energy)) + 1e-9
        assert best.energy == min(float(np.min(s.energy)) for s in tr)
    # best-so-far spins actually evaluate to the reported energy
    r0 = results[0]
    best0 = stream.best(r0.jid)
    m = MODEL
    from repro.core import observables

    assert np.isclose(float(observables.energies(m, best0.spins)),
                      best0.energy)
    stream.forget(r0.jid)
    assert stream.trace(r0.jid) == [] and stream.best(r0.jid) is None


def test_stream_trace_window_is_bounded():
    stream = ObservableStream(trace_window=4)
    srv = _server(slots=1, chunk_sweeps=1, stream=stream, policy="fifo")
    srv.submit(AnnealJob.constant(seed=3, sweeps=20, beta=1.0))
    (r,) = srv.drain()
    tr = stream.trace(r.jid)
    assert len(tr) == 4  # bounded, keeps the most recent chunks
    assert [s.sweeps_done for s in tr] == [17, 18, 19, 20]


# -----------------------------------------------------------------------------
# Launch-skew detection.
# -----------------------------------------------------------------------------


def test_skew_monitor_flags_straggling_device():
    mon = LaunchSkewMonitor(num_devices=4, warmup_steps=3)
    rng = np.random.default_rng(0)
    for _ in range(10):
        assert mon.record(0.010 + rng.normal(0, 1e-4, 4)) == []
    # device 2 suddenly runs 5x slower than its peers
    times = np.full(4, 0.010)
    times[2] = 0.050
    assert mon.record(times) == [2]
    ev = mon.events[-1]
    assert ev.device == 2 and ev.seconds == 0.050
    assert abs(ev.device_median - 0.010) < 1e-6
    # healthy launches afterwards stay quiet (no EMA poisoning)
    for _ in range(5):
        assert mon.record(0.010 + rng.normal(0, 1e-4, 4)) == []


def test_skew_monitor_ignores_microsecond_jitter():
    """Near-instant launches jitter by factors, not by meaningful time:
    the absolute min-gap floor keeps them quiet."""
    mon = LaunchSkewMonitor(num_devices=4, warmup_steps=2)
    for _ in range(20):
        times = np.array([1e-6, 2e-6, 5e-6, 1e-5])  # 10x spread, all tiny
        assert mon.record(times) == []


def test_skew_monitor_validates_shape():
    mon = LaunchSkewMonitor(num_devices=4)
    with pytest.raises(ValueError):
        mon.record(np.zeros(3))
    with pytest.raises(ValueError):
        LaunchSkewMonitor(num_devices=0)
    with pytest.raises(ValueError):
        LaunchSkewMonitor(num_devices=2, rel_threshold=1.0)


# -----------------------------------------------------------------------------
# Profiler window.
# -----------------------------------------------------------------------------


def test_profiler_window_spans_n_chunks(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda logdir: calls.append(("start", logdir)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    srv = _server(slots=1, chunk_sweeps=1, policy="fifo")
    srv.arm_profiler(tmp_path / "prof", num_chunks=3)
    srv.submit(AnnealJob.constant(seed=5, sweeps=8, beta=1.0))
    srv.drain()
    assert calls == [("start", str(tmp_path / "prof")), ("stop",)]
    names = [e["name"] for e in srv.telemetry.events()]
    i_start = names.index("profiler.start")
    i_stop = names.index("profiler.stop")
    launches = [i for i, n in enumerate(names) if n == "engine.launch"]
    # exactly 3 launches land inside the window
    assert len([i for i in launches if i_start < i < i_stop]) == 3
    assert srv._profiler is None  # disarmed after the window
    with pytest.raises(ValueError):
        srv.arm_profiler(tmp_path, num_chunks=0)


def test_profiler_failure_never_kills_serving(monkeypatch, tmp_path):
    def boom(logdir):
        raise RuntimeError("profiler unavailable")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    srv = _server(slots=1, chunk_sweeps=2, policy="fifo")
    srv.arm_profiler(tmp_path / "prof")
    srv.submit(AnnealJob.constant(seed=5, sweeps=4, beta=1.0))
    # A profile that was asked for and not taken raises to the caller ...
    with pytest.raises(ProfilerError, match="unavailable") as err:
        srv.drain()
    assert err.value.results == []
    assert srv._profiler is None and srv.sweeps_elapsed == 0
    # ... and the server, left consistent and disarmed, serves on.
    (r,) = srv.drain()
    assert r.sweeps_done == 4
    names = [e["name"] for e in srv.telemetry.events()]
    assert "profiler.start" not in names


@pytest.mark.parametrize("num_chunks", [1, 2])
def test_profiler_stop_failure_raises_after_the_step(monkeypatch, tmp_path, num_chunks):
    def boom():
        raise RuntimeError("stop failed")

    monkeypatch.setattr(jax.profiler, "start_trace", lambda logdir: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", boom)
    srv = _server(slots=1, chunk_sweeps=2, policy="fifo")
    srv.arm_profiler(tmp_path / "prof", num_chunks=num_chunks)
    first = srv.submit(AnnealJob.constant(seed=5, sweeps=2, beta=1.0))
    second = srv.submit(AnnealJob.constant(seed=6, sweeps=4, beta=1.0))
    with pytest.raises(ProfilerError, match="stop failed") as err:
        srv.drain()
    # The failing step finished its bookkeeping before the stop raised,
    # and the job that retired first -- in the failing step (1 chunk) or
    # in an earlier step of the same drain (2) -- is in the error.
    assert srv._profiler is None and srv.sweeps_elapsed == 2 * num_chunks
    assert [r.jid for r in err.value.results] == [first]
    (r,) = srv.drain()
    assert r.jid == second and r.sweeps_done == 4
