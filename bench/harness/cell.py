"""One run of one cell: build the served system, warm it up, measure a
window on its served path, then check what the window produced.

The program under test is the sampling service (`repro.serve_mc`):
`SampleServer.submit` / `step` with the configuration's `ServeConfig`
fields.  Everything else here (instances, traffic, timing, reference,
comparison) belongs to the benchmark.  What depends on the kind of job
the mix sends (its specs, the program's job, the outputs at the close
and their replay) is the kind's module, ``bench/kinds/<kind>.py``.
"""

from __future__ import annotations

import collections
import gc
import time

import jax
import numpy as np

from harness import check, models, reference, spec as spec_mod

TRACE_SECONDS = 3.0  # length of the profiled stretch of a traced window
DRAIN_GRACE_S = 120.0  # how long an open loop waits past the window for due jobs


def _annotate(name: str):
    return jax.profiler.TraceAnnotation(name)


class CompileCounter:
    """Counts JAX compile requests and backend compiles while armed."""

    def __init__(self):
        self.armed = False
        self.counts = collections.Counter()
        import jax.monitoring as mon

        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name.endswith("compile_requests_use_cache") and self.armed:
            self.counts["requests_window"] += 1
        elif name.endswith("cache_hits"):
            self.counts["cache_hits"] += 1
        elif name.endswith("cache_misses"):
            self.counts["cache_misses"] += 1

    def _duration(self, name, _secs, **_):
        if name.endswith("backend_compile_duration") and self.armed:
            self.counts["backend_window"] += 1

    def arm(self) -> None:
        """Count compiles from now on, afresh: a run's window."""
        self.counts["requests_window"] = self.counts["backend_window"] = 0
        self.armed = True


class Served:
    """The system under test, with the bench's view of its jobs."""

    def __init__(self, cfg: dict, chips: int, insts: list, kind):
        from repro.core import ising
        from repro.serve_mc import SampleServer, ServeConfig

        self.insts = insts
        self.models = [ising.LayeredModel(
            n=m["n"], L=m["L"], h=m["h"], space_nbr=m["space_nbr"],
            space_J=m["space_J"], tau_J=m["tau_J"], beta=m["beta"]) for m in insts]
        fields = dict(cfg["serve"])
        fields["slots"] = cfg["slots_per_chip"] * chips
        if chips > 1:
            from repro.launch.mesh import make_slot_mesh

            fields["mesh"] = make_slot_mesh(chips)
        self.server = SampleServer(self.models[0], config=ServeConfig(**fields))
        self.multi = bool(fields.get("multi_tenant"))
        self.kind = kind
        self.jobs = {}  # jid -> (spec, job)

    def submit(self, spec: dict):
        job = self.kind.make_job(self, spec)
        with _annotate("bench.submit"):
            self.server.submit(job)
        self.jobs[job.jid] = (spec, job)
        return job

    def step(self):
        with _annotate("bench.step"):
            return self.server.step()

    def slots_of(self, job) -> tuple:
        return tuple(self.server._active[job.jid][1])


class Loop:
    """Drives the server under the mix: a closed backlog or an open loop."""

    def __init__(self, served: Served, mix: dict, chips: int, seed: int):
        self.served = served
        self.mix = mix
        self.open = mix["loop"] == "open"
        self.retired = {}  # jid -> (result, t_retired)
        self.lateness = []
        self.t0 = None
        self.pending = collections.deque()  # an open loop's next spec
        specs = served.kind.specs(mix, chips, seed, len(served.insts))
        if isinstance(specs, list):  # a closed, fixed set of jobs
            self.stream = None
            for spec in specs:
                served.submit(spec)
        else:
            self.stream = specs

    def start(self) -> None:
        self.t0 = time.perf_counter()

    def _feed(self, now: float) -> None:
        srv = self.served.server
        if self.stream is None:
            return
        if not self.open:
            while srv.num_queued < srv.slots * self.mix["queue_min_pools"]:
                self.served.submit(self.stream.next())
            return
        while True:
            if not self.pending:
                self.pending.append(self.stream.next())
            spec = self.pending[0]
            due = self.t0 + spec["arrival"]
            if due > now:
                return
            self.pending.popleft()
            spec["due"] = due
            self.served.submit(spec)
            self.lateness.append(time.perf_counter() - due)

    def tick(self) -> None:
        """Feed what is due, then one scheduling step (or a wait)."""
        srv = self.served.server
        self._feed(time.perf_counter())
        if not srv.num_active and not srv.num_queued:
            with _annotate("bench.wait"):
                nxt = self.t0 + self.pending[0]["arrival"] if self.pending else None
                if nxt is not None:
                    time.sleep(max(0.0, min(nxt - time.perf_counter(), 0.05)))
            return
        done = self.served.step()
        t = time.perf_counter()
        for r in done:
            self.retired[r.jid] = (r, t)

    def progress(self) -> dict:
        """Sweeps done by every job the bench has submitted, by jid."""
        return {jid: job.sweeps_done for jid, (_, job) in self.served.jobs.items()}


def run(cell: dict, cfg: dict, mix: dict, seed: int, seconds: float, trace: bool,
        t_process: float, counter: CompileCounter, trace_dir: str | None = None) -> dict:
    """One whole run; returns the raw record the reporting step reads."""
    chips = cell["chips"]
    kind = spec_mod.kind(mix["kind"])
    insts = models.instances(cfg, seed)
    served = Served(cfg, chips, insts, kind)
    srv = served.server
    loop = Loop(served, mix, chips, seed)
    spins_per_replica = insts[0]["n"] * insts[0]["L"]

    # Warm-up: every program the window uses is compiled (or loaded) here.
    loop.start()
    while srv.sweeps_elapsed < mix["warmup_sweeps"]:
        loop.tick()
    jax.block_until_ready(srv.carry)
    setup_s = time.perf_counter() - t_process

    # The measured window.
    before = loop.progress()
    busy0 = srv.busy_slot_sweeps
    retired0 = set(loop.retired)
    counter.arm()
    tracing = None
    if trace:
        from harness import tracing as tr

        tracing = tr.Window(trace_dir, srv)
        tracing.start()
    ticks = []  # (seconds into the window, busy slot-sweeps launched so far)
    t_open = time.perf_counter()
    if loop.open:
        loop.window = (t_open - loop.t0, t_open - loop.t0 + seconds)
    while True:
        if tracing is not None and tracing.active and time.perf_counter() - t_open >= TRACE_SECONDS:
            tracing.stop()
        if time.perf_counter() - t_open >= seconds:
            break
        loop.tick()
        ticks.append((time.perf_counter() - t_open, srv.busy_slot_sweeps - busy0))
    jax.block_until_ready(srv.carry)
    t_close = time.perf_counter()
    if tracing is not None and tracing.active:
        tracing.stop()
    if loop.open:
        loop._feed(time.perf_counter())  # every job due by the close is submitted
    counter.armed = False
    after = loop.progress()
    busy1 = srv.busy_slot_sweeps
    window_s = t_close - t_open

    rec = dict(setup_s=setup_s, window_s=window_s, spins_per_replica=spins_per_replica,
               compiles_window=counter.counts["requests_window"],
               backend_compiles_window=counter.counts["backend_window"])
    slots_of = {jid: job.num_slots for jid, (_, job) in served.jobs.items()}
    rec["spin_updates"] = sum((after[j] - before.get(j, 0)) * slots_of[j]
                              for j in after) * spins_per_replica
    rec["busy_slot_sweeps"] = busy1 - busy0
    rec["fifths"] = _fifths(ticks, window_s)
    rec["retired_window"] = [j for j in loop.retired if j not in retired0]
    if loop.open:
        lo, hi = loop.window
        due = [jid for jid, (spec, _) in served.jobs.items()
               if spec.get("arrival") is not None and lo <= spec["arrival"] < hi]
        deadline = t_close + DRAIN_GRACE_S
        while any(j not in loop.retired for j in due) and time.perf_counter() < deadline:
            loop.tick()
        lat = [loop.retired[j][1] - served.jobs[j][0]["due"] for j in due if j in loop.retired]
        rec.update(due=due, latencies=lat,
                   lost=[j for j in due if j not in loop.retired],
                   lateness=loop.lateness)
    rec["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()[:chips])
    if tracing is not None:
        rec["trace_record"] = tracing.record
        rec["trace"] = tracing.reduce()

    # What the window produced, for the check.
    rng = np.random.default_rng([seed, 3])
    pool = [j for j in (rec["due"] if loop.open else rec["retired_window"]) if j in loop.retired]
    outputs = kind.outputs(served, loop, pool, mix, rng)
    if loop.stream is None:
        rec["attempted"], rec["failed"] = len(served.jobs), 0
    elif loop.open:
        rec["attempted"], rec["failed"] = len(rec["due"]), len(rec["lost"])
    else:
        rec["attempted"], rec["failed"] = len(rec["retired_window"]), 0
    lattice = (insts[0]["n"], insts[0]["L"], srv.engine.V, insts[0]["space_nbr"])
    lost = len(rec.get("lost", []))
    del loop, served, srv
    gc.collect()

    # The reference runs only now, with the program's state freed.
    t_ref = time.perf_counter()
    rec["checks"] = check.compare(outputs, reference.Lattice(*lattice), insts, lost, kind)
    rec["reference_s"] = time.perf_counter() - t_ref
    return rec


def _fifths(ticks: list, window_s: float) -> list:
    """Busy slot-sweeps launched in each fifth of the window: whether the
    rate drifts inside a run or only from run to run."""
    edges = [window_s * k / 5 for k in range(6)]
    last, out = 0, []
    for hi in edges[1:]:
        upto = max([n for t, n in ticks if t <= hi], default=last)
        out.append(upto - last)
        last = upto
    return out
