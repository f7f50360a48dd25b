"""SampleServer throughput: packed continuous batching vs one job at a time.

The serving claim of DESIGN.md §Service, measured three ways:

* ``a4`` rung (the paper's sequential-order sweep): 32 mixed-budget
  constant-beta jobs through a packed server (slots=8 and 16) vs the same
  scheduler with ``slots=1`` — the sequential B=1 baseline, a single
  *resident* engine serving jobs one at a time (the status quo before the
  serving layer; a fresh-engine-per-job baseline would additionally pay
  ~1 s of retrace per job and is not interesting to time).
* ``cb`` rung (graph-colored sweeps, the serving default): same
  comparison where per-sweep cost no longer dwarfs scheduler overhead —
  the honest measure of the scheduler itself (ROADMAP serve-bench-on-cb).
* heterogeneous models (``multi_tenant=True``, cb rung): the same 32 jobs
  spread round-robin over 8 DIFFERENT models of one lattice (reseeded
  disorder), packed into one multi-tenant server vs a resident slots=1
  server serving each job's model in turn — the multi-tenant claim of
  DESIGN.md §Multi-tenancy (packed >= 2x is the ISSUE 4 acceptance bar).
* mesh-sharded slot pool (cb rung): the SAME equal-budget job mix served
  at D in {1, 2, 4} forced host devices (``make_slot_mesh(D)``, slots =
  4*D), all in this one process over the devices present (on the CPU,
  force them: ``XLA_FLAGS=--xla_force_host_platform_device_count=4``;
  sections that need more devices than are present print a skip row).
  Per-job results must hash identically
  across D (the DESIGN.md §Mesh bit-exactness contract), and the
  DETERMINISTIC sweep-clock throughput — jobs per global sweep — must
  scale with the pool: at 4x slots the mix drains in 1/4 the sweeps, so
  the asserted D=4 >= 2x D=1 bar holds on any machine, including this
  single-core box where forced host devices cannot show wall speedup
  (wall ``speedup_vs_D1`` is reported and baseline-gated, not asserted).
* placement (cb rung, D=4 forced host devices): device-affine admission
  vs the flat (legacy lowest-index) free list on a PT-heavy mix of R=2
  ladders — affine must execute strictly fewer cross-device swap
  gathers with BIT-IDENTICAL per-job results (ISSUE 9 acceptance); the
  deterministic ``cross_swap_ratio`` is gated by check_regression.
* heterogeneous mesh (cb rung, D=4 forced host devices): the same job
  mix — including a 6-replica PT ladder wider than any single device —
  on an UNEVEN ``capacities=[4,2,1,1]`` slot pool vs the single-device
  engine with the same 8 global slots (ISSUE 10 acceptance): per-job
  results must hash identically, the ladder must actually span devices,
  and the deterministic ``jobs_per_sweep_vs_D1`` ratio (gated by
  check_regression) must stay at 1.0 — an uneven vector is pure layout
  and must not perturb admission timing.
* telemetry overhead (cb rung): the same mix with the full observability
  event pipeline on vs telemetry off, interleaved rounds — measures the
  DESIGN.md §Observability <= 5% overhead claim as ``overhead_ratio``
  (jobs/sec on / off), gated against the baseline by check_regression.
* scheduling policies (cb rung): one ADVERSARIAL wide+narrow mixed
  workload — narrow starters, a 6-slot PT ladder near the queue head
  (head-of-line blocker), a heavy user's narrow backlog with a light
  user sprinkled in, and one urgent (priority 2) wide ladder submitted
  last — served under ``policy="fifo"`` vs ``"backfill"`` vs ``"fair"``
  (DESIGN.md §Scheduling).  Reports jobs/sec, p50/p95 queue wait, slot
  utilization, the urgent job's wait, and preemption counts; asserts the
  ISSUE 5 acceptance bar (backfill and fair beat FIFO on jobs/sec AND
  p95 wait) and that per-job results are BIT-IDENTICAL across policies.

Measured on CPU (the engine's jnp execution path; the Pallas backend on
CPU runs the kernel in interpret mode, which evaluates the kernel body in
Python per replica tile and therefore cannot amortize the batch — it is a
correctness path, reported separately by kernel_bench).  The packed
speedup comes from per-launch dispatch overhead amortized over B resident
jobs and the vmapped sweep filling vector width a single V=4 replica
leaves idle (the paper's batching insight applied to user jobs).

Every packed path must produce BIT-IDENTICAL per-job spins to its
sequential baseline — verified on every run; a mismatch raises.

Emits BENCH_serve.json (schema: name, B, sweeps_per_sec, wall_clock_s,
plus jobs_per_sec / spin_flips_per_sec / speedup_vs_B1).

Run:  PYTHONPATH=src python -m benchmarks.serve_bench
"""

from __future__ import annotations

import hashlib
import time
from collections import defaultdict

import jax
import numpy as np

from benchmarks.common import write_bench_json
from repro.core import ising
from repro.serve_mc import AnnealJob, PTJob, SampleServer, make_policy

NUM_JOBS = 32
CHUNK = 8
MODEL_N, MODEL_L, V = 16, 32, 4
SLOT_CONFIGS = (8, 16)
NUM_TENANT_MODELS = 8
SCHED_SLOTS = 8
SCHED_POLICIES = ("fifo", "backfill", "fair")
# The sched section runs a LARGER lattice (same n, deeper L) so per-sweep
# compute dominates launch dispatch and wall clock tracks the sweep-clock
# scheduling wins instead of burying them in per-launch overhead.
SCHED_MODEL_L = 128
# Sharded section: slots scale with the device count, budgets are EQUAL so
# the drain schedule is uniform waves and the sweep-clock ratio is exact.
SHARDED_DEVICE_COUNTS = (1, 2, 4)
SHARDED_SLOTS_PER_DEVICE = 4
SHARDED_NUM_JOBS = 32
SHARDED_JOB_SWEEPS = 8 * CHUNK
SHARDED_MODEL_L = 32
# Placement section: D=4 forced devices, 2 slots per device (cap=2), so
# every R=2 PT ladder fits on one device — affine placement keeps the
# round-boundary swap gathers in-device while the flat free list lets
# ladders straddle device boundaries.
PLACEMENT_DEVICES = 4
PLACEMENT_SLOTS_PER_DEVICE = 2
PLACEMENT_NUM_LADDERS = 6
PLACEMENT_PT_ROUNDS = 8


def job_specs(num_jobs: int, seed: int, chunk: int):
    """Mixed budgets: 4-16 chunks of sweeps per job, scattered betas."""
    rng = np.random.default_rng(seed)
    return [
        (
            1000 + i,
            int(rng.integers(4, 17)) * chunk,
            float(rng.uniform(0.5, 1.5)),
        )
        for i in range(num_jobs)
    ]


REPEATS = 3  # best-of-N rounds per workload: the box this runs on is shared
# The sched section's fair-vs-fifo WALL margin is thin (~1.5%: fair's
# sweep-clock win is partly spent on park/resume dispatches), while
# per-round jitter on a shared box runs ~10% — so its acceptance
# assertions need more interleaved best-of rounds than the throughput
# sections.  Rounds are ~0.13 s each; the extra de-noising costs ~3 s.
SCHED_REPEATS = 8
# The telemetry section gates a ratio of two nearly-equal walls (target
# >= 0.95 of telemetry-off), so it needs the same extra de-noising.
TELEMETRY_REPEATS = 8
# Periodic-snapshot cadence for the snapshot_overhead section: ~12
# background snapshots across one ~2.5k-sweep round — dense enough that
# a snapshot gone blocking shows up in the wall, sparse enough to model
# a real crash-safety cadence.
SNAPSHOT_EVERY_SWEEPS = 200


def run_workload(m, specs, slots: int, chunk: int, *, rung: str = "a4",
                 models=None, repeats: int = REPEATS):
    """Serve the whole spec list through one resident server; returns
    (results by submission order, wall seconds, busy sweeps, launches).

    ``models`` (heterogeneous mode) assigns job i the model
    ``models[i % len(models)]`` and serves through a multi-tenant server.
    The spec list is served ``repeats`` times through the SAME resident
    server (steady-state traffic) and the fastest round is reported —
    determinism makes every round's results bit-identical, so repetition
    only de-noises the wall clock.
    """
    # telemetry=False: the comparison sections measure the untimed
    # fire-and-forget hot path (per-launch event timing would add a sync
    # whose cost scales with launch count, skewing path-vs-path ratios);
    # the telemetry_overhead section is where "on" is measured.
    srv = SampleServer(
        m, slots=slots, chunk_sweeps=chunk, backend="jnp", V=V, rung=rung,
        multi_tenant=models is not None, telemetry=False,
    )
    # Warmup: pay jit for run(chunk)/splice/extract outside the timed window.
    srv.submit(AnnealJob.constant(seed=1, sweeps=chunk, beta=1.0))
    srv.drain()
    dt = float("inf")
    for _ in range(repeats):
        base_sweeps = srv.stats()["busy_slot_sweeps"]
        base_launches = srv.launches
        jobs = [
            AnnealJob.constant(
                seed=s, sweeps=b, beta=be,
                model=None if models is None else models[i % len(models)],
            )
            for i, (s, b, be) in enumerate(specs)
        ]
        t0 = time.perf_counter()
        for j in jobs:
            srv.submit(j)
        by_jid = {r.jid: r for r in srv.drain()}
        round_dt = time.perf_counter() - t0
        results = [by_jid[j.jid] for j in jobs]
        busy = srv.stats()["busy_slot_sweeps"] - base_sweeps
        launches = srv.launches - base_launches
        dt = min(dt, round_dt)
    return results, dt, busy, launches


def _check_bit_identical(seq_res, packed_res, specs, label: str):
    for i, (r_seq, r_pack) in enumerate(zip(seq_res, packed_res)):
        if not np.array_equal(r_seq.spins, r_pack.spins):
            raise AssertionError(
                f"{label}: packed result differs from sequential for job "
                f"seed/budget {specs[i]}"
            )


def _compare_section(m, specs, section: str, slot_configs, *, rung: str,
                     models=None, rows=None, records=None):
    """One packed-vs-sequential comparison; appends records and CSV rows."""
    total_sweeps = sum(b for _, b, _ in specs)
    n_spins = m.num_spins
    seq_res, seq_dt, seq_sweeps, _launches = run_workload(
        m, specs, slots=1, chunk=CHUNK, rung=rung, models=models
    )
    assert seq_sweeps == total_sweeps
    records.append(
        {
            "name": f"{section}_sequential",
            "B": 1,
            "rung": rung,
            "sweeps_per_sec": total_sweeps / seq_dt,
            "wall_clock_s": seq_dt,
            "jobs_per_sec": NUM_JOBS / seq_dt,
            "spin_flips_per_sec": total_sweeps * n_spins / seq_dt,
            "num_jobs": NUM_JOBS,
            "num_models": 1 if models is None else len(models),
        }
    )
    rows.append(
        (f"{section}_seq_B1_jobs_per_sec", NUM_JOBS / seq_dt * 1e6,
         f"{NUM_JOBS / seq_dt:.1f} jobs/s, {seq_dt:.2f}s wall")
    )
    for slots in slot_configs:
        res, dt, _busy, launches = run_workload(
            m, specs, slots=slots, chunk=CHUNK, rung=rung, models=models
        )
        _check_bit_identical(seq_res, res, specs, f"{section} slots={slots}")
        speedup = seq_dt / dt
        records.append(
            {
                "name": f"{section}_packed_B{slots}",
                "B": slots,
                "rung": rung,
                "sweeps_per_sec": total_sweeps / dt,
                "wall_clock_s": dt,
                "jobs_per_sec": NUM_JOBS / dt,
                "spin_flips_per_sec": total_sweeps * n_spins / dt,
                "speedup_vs_B1": speedup,
                "launches": launches,
                "bit_identical_to_B1": True,
                "num_jobs": NUM_JOBS,
                "num_models": 1 if models is None else len(models),
            }
        )
        rows.append(
            (f"{section}_packed_B{slots}_jobs_per_sec", NUM_JOBS / dt * 1e6,
             f"{NUM_JOBS / dt:.1f} jobs/s = {speedup:.2f}x vs B=1, "
             f"bit-identical, {launches} launches")
        )


def _have_devices(need: int, section: str, rows) -> bool:
    """Whether ``need`` devices are present; if not, a skip row says so."""
    have = len(jax.devices())
    if have < need:
        rows.append((section, 0.0,
                     f"skipped: needs {need} devices, found {have} (on the "
                     "CPU: XLA_FLAGS=--xla_force_host_platform_device_count"
                     f"={need})"))
    return have >= need


def _serve_sharded(d: int) -> dict:
    """Serve the fixed equal-budget mix on a D-device ("data",) mesh."""
    from repro.launch.mesh import make_slot_mesh

    m = ising.random_layered_model(n=MODEL_N, L=SHARDED_MODEL_L, seed=0, beta=1.0)
    slots = SHARDED_SLOTS_PER_DEVICE * d
    srv = SampleServer(
        m, slots=slots, chunk_sweeps=CHUNK, backend="jnp", V=V, rung="cb",
        mesh=make_slot_mesh(d), telemetry=False,
    )
    # Warmup pays jit for run(chunk) + splice/extract outside the timing.
    srv.submit(AnnealJob.constant(seed=1, sweeps=CHUNK, beta=1.0))
    srv.drain()
    best = None
    for _ in range(REPEATS):
        base = srv.stats()["sweeps_elapsed"]
        jobs = [
            AnnealJob.constant(seed=2000 + i, sweeps=SHARDED_JOB_SWEEPS,
                               beta=0.5 + i / SHARDED_NUM_JOBS)
            for i in range(SHARDED_NUM_JOBS)
        ]
        t0 = time.perf_counter()
        for j in jobs:
            srv.submit(j)
        by_jid = {r.jid: r for r in srv.drain()}
        dt = time.perf_counter() - t0
        sweeps = srv.stats()["sweeps_elapsed"] - base
        h = hashlib.sha256()
        for j in jobs:
            r = by_jid[j.jid]
            h.update(np.ascontiguousarray(r.spins).tobytes())
            h.update(np.float64(r.energy).tobytes())
        out = {
            "D": d,
            "slots": slots,
            "wall_s": dt,
            "sweeps_elapsed": int(sweeps),
            "jobs_per_sweep": SHARDED_NUM_JOBS / sweeps,
            "jobs_per_sec": SHARDED_NUM_JOBS / dt,
            "spins_sha256": h.hexdigest(),
        }
        # Sweeps and the hash are deterministic; best-of only de-noises wall.
        if best is None or dt < best["wall_s"]:
            best = out
    return best


def _sharded_section(rows, records):
    """Slot-parallel sweeps over a device mesh at D in {1, 2, 4}.

    Asserts the DESIGN.md §Mesh contract in-bench: identical per-job
    result hashes across D, and the deterministic sweep-clock throughput
    bar jobs_per_sweep(D=4) >= 2x D=1 (4x slots drain the equal-budget
    mix in 1/4 the global sweeps, so the true ratio is 4.0 exactly).
    """
    if not _have_devices(max(SHARDED_DEVICE_COUNTS), "serve_sharded", rows):
        return
    outs = {d: _serve_sharded(d) for d in SHARDED_DEVICE_COUNTS}
    ref = outs[SHARDED_DEVICE_COUNTS[0]]
    for d, o in outs.items():
        if o["spins_sha256"] != ref["spins_sha256"]:
            raise AssertionError(
                f"sharded D={d}: per-job results differ from D=1 "
                "(bit-exactness contract broken)"
            )
    ratio4 = outs[4]["jobs_per_sweep"] / ref["jobs_per_sweep"]
    if ratio4 < 2.0:
        raise AssertionError(
            f"sharded acceptance: D=4 jobs/sweep is {ratio4:.2f}x D=1 "
            "(needs >= 2x at 4x slots)"
        )
    for d in SHARDED_DEVICE_COUNTS:
        o = outs[d]
        rec = {
            "name": f"serve_sharded_D{d}",
            "B": o["slots"],
            "rung": "cb",
            "devices": d,
            "sweeps_per_sec": o["sweeps_elapsed"] / o["wall_s"],
            "wall_clock_s": o["wall_s"],
            "jobs_per_sec": o["jobs_per_sec"],
            "jobs_per_sweep": o["jobs_per_sweep"],
            "sweeps_elapsed": o["sweeps_elapsed"],
            "num_jobs": SHARDED_NUM_JOBS,
            "bit_identical_to_D1": True,
        }
        if d != SHARDED_DEVICE_COUNTS[0]:
            rec["jobs_per_sweep_vs_D1"] = (
                o["jobs_per_sweep"] / ref["jobs_per_sweep"]
            )
            rec["speedup_vs_D1"] = ref["wall_s"] / o["wall_s"]
        records.append(rec)
        extra = ("" if d == SHARDED_DEVICE_COUNTS[0] else
                 f", {rec['jobs_per_sweep_vs_D1']:.1f}x jobs/sweep vs D=1, "
                 f"{rec['speedup_vs_D1']:.2f}x wall")
        rows.append(
            (f"serve_sharded_D{d}_jobs_per_sec", o["jobs_per_sec"] * 1e6,
             f"{o['jobs_per_sec']:.1f} jobs/s over {o['slots']} slots on "
             f"{d} devices, {o['sweeps_elapsed']} sweeps{extra}")
        )


def _placement_jobs():
    """PT-heavy mix: R=2 ladders interleaved with mixed-budget anneals.

    The interleaving is the point: under ``placement="flat"`` (lowest
    global slot indices, the pre-placement behaviour) the first ladder
    lands on slots (1, 2) — straddling the device boundary at D=4 with
    2 slots per device — and the staggered anneal budgets keep the free
    list fragmented so later ladders straddle too.  Device-affine
    placement packs every R=2 ladder onto one device instead (cap is 2),
    so its round-boundary swaps take the in-device fast path.
    """
    jobs = []
    for i in range(PLACEMENT_NUM_LADDERS):
        jobs.append(AnnealJob.constant(
            seed=3000 + i, sweeps=(3 + 2 * (i % 3)) * CHUNK, beta=0.8))
        jobs.append(PTJob(
            seed=3100 + i, betas=[0.6, 1.0],
            num_rounds=PLACEMENT_PT_ROUNDS, sweeps_per_round=CHUNK))
    return jobs


def _serve_placement(mode: str) -> dict:
    """Serve the PT-heavy mix at D=4 under one placement mode."""
    from repro.launch.mesh import make_slot_mesh

    d = PLACEMENT_DEVICES
    m = ising.random_layered_model(n=MODEL_N, L=SHARDED_MODEL_L, seed=0, beta=1.0)
    slots = PLACEMENT_SLOTS_PER_DEVICE * d
    srv = SampleServer(
        m, slots=slots, chunk_sweeps=CHUNK, backend="jnp", V=V, rung="cb",
        mesh=make_slot_mesh(d), telemetry=False, placement=mode,
    )
    # Warmup pays jit for run(chunk) + splice/extract outside the timing.
    srv.submit(AnnealJob.constant(seed=1, sweeps=CHUNK, beta=1.0))
    srv.drain()
    base = srv.stats()["placement"]
    best, counters = None, None
    for _ in range(REPEATS):
        jobs = _placement_jobs()
        sweeps0 = srv.stats()["sweeps_elapsed"]
        t0 = time.perf_counter()
        for j in jobs:
            srv.submit(j)
        by_jid = {r.jid: r for r in srv.drain()}
        dt = time.perf_counter() - t0
        sweeps = srv.stats()["sweeps_elapsed"] - sweeps0
        if counters is None:
            # Placement decisions and swap routing are deterministic per
            # round; the first round's counter deltas are THE counts.
            st = srv.stats()["placement"]
            counters = {k: st[k] - base[k]
                        for k in ("affine", "spanning", "rebalance_migrations",
                                  "pt_swap_local", "pt_swap_cross")}
        h = hashlib.sha256()
        for j in jobs:
            r = by_jid[j.jid]
            h.update(np.ascontiguousarray(r.spins).tobytes())
            h.update(np.float64(r.energy).tobytes())
        out = {
            "placement": mode,
            "slots": slots,
            "num_jobs": len(jobs),
            "wall_s": dt,
            "sweeps_elapsed": int(sweeps),
            "jobs_per_sec": len(jobs) / dt,
            "spins_sha256": h.hexdigest(),
            **counters,
        }
        # Counters and the hash are deterministic; best-of de-noises wall.
        if best is None or dt < best["wall_s"]:
            best = out
    return best


def _placement_section(rows, records):
    """Device-affine vs flat slot placement on the PT-heavy mix at D=4.

    Asserts the ISSUE 9 acceptance bar in-bench: affine placement
    executes strictly fewer cross-device swap gathers than the flat
    (legacy lowest-index) free list on the same workload, and per-job
    results are BIT-IDENTICAL — placement decides WHERE, never WHAT.
    The gated ``cross_swap_ratio`` (affine cross swaps / flat cross
    swaps) is deterministic: 0.0 as long as the rebalancer keeps every
    R=2 ladder device-local.
    """
    if not _have_devices(PLACEMENT_DEVICES, "serve_placement_D4", rows):
        return
    outs = {mode: _serve_placement(mode) for mode in ("affine", "flat")}
    a, f = outs["affine"], outs["flat"]
    if a["spins_sha256"] != f["spins_sha256"]:
        raise AssertionError(
            "placement acceptance: affine vs flat per-job results differ "
            "(placement must not change WHAT, only WHERE)"
        )
    if a["pt_swap_cross"] >= f["pt_swap_cross"]:
        raise AssertionError(
            f"placement acceptance: affine cross-device swap gathers "
            f"({a['pt_swap_cross']}) not below flat ({f['pt_swap_cross']})"
        )
    swaps = a["pt_swap_local"] + a["pt_swap_cross"]
    rec = {
        "name": "serve_placement_D4",
        "B": a["slots"],
        "rung": "cb",
        "devices": PLACEMENT_DEVICES,
        "num_jobs": a["num_jobs"],
        "wall_clock_s": a["wall_s"],
        "sweeps_per_sec": a["sweeps_elapsed"] / a["wall_s"],
        "jobs_per_sec": a["jobs_per_sec"],
        "jobs_per_sec_flat": f["jobs_per_sec"],
        "pt_swap_cross_affine": a["pt_swap_cross"],
        "pt_swap_cross_flat": f["pt_swap_cross"],
        "pt_swap_local_affine": a["pt_swap_local"],
        "cross_swap_ratio": a["pt_swap_cross"] / max(1, f["pt_swap_cross"]),
        "local_swap_fraction": a["pt_swap_local"] / max(1, swaps),
        "spanning_placements_affine": a["spanning"],
        "rebalance_migrations_affine": a["rebalance_migrations"],
        "bit_identical_to_flat": True,
    }
    records.append(rec)
    rows.append(
        ("serve_placement_D4_cross_swaps",
         float(a["pt_swap_cross"]),
         f"{a['pt_swap_cross']} cross-device swap gathers (affine) vs "
         f"{f['pt_swap_cross']} (flat) over {swaps} PT swaps, "
         f"{a['rebalance_migrations']} migrations, bit-identical")
    )


# Heterogeneous mesh section: D=4 forced devices with an UNEVEN capacity
# vector (one big host-like device, one medium, two small) over the same
# 8 global slots as the single-device reference.  The mix includes a
# 6-replica PT ladder wider than any device's capacity, so it MUST span
# devices on the ragged pool — exercising the cross-device swap path and
# ragged park/resume, not just the happy affine case.
HETERO_CAPACITIES = (4, 2, 1, 1)
HETERO_NUM_ROUNDS = 6


def _hetero_jobs():
    """Deterministic mix over 8 slots: wide spanning ladder + anneals."""
    jobs = [PTJob(seed=4000, betas=np.linspace(0.5, 1.5, 6).astype(np.float32),
                  num_rounds=HETERO_NUM_ROUNDS, sweeps_per_round=CHUNK)]
    for i in range(8):
        jobs.append(AnnealJob.constant(
            seed=4100 + i, sweeps=(2 + (i % 4)) * CHUNK,
            beta=0.6 + 0.1 * i))
    jobs.append(PTJob(seed=4200, betas=[0.7, 1.1],
                      num_rounds=4, sweeps_per_round=CHUNK))
    return jobs


def _serve_hetero(layout: str) -> dict:
    """Serve the hetero mix under one layout ("hetero" = D=4 mesh with
    capacities [4,2,1,1]; "d1" = single device, same 8 global slots)."""
    from repro.launch.mesh import make_slot_mesh

    m = ising.random_layered_model(n=MODEL_N, L=SHARDED_MODEL_L, seed=0,
                                   beta=1.0)
    kw = {}
    if layout == "hetero":
        kw = dict(mesh=make_slot_mesh(len(HETERO_CAPACITIES)),
                  capacities=HETERO_CAPACITIES)
    srv = SampleServer(
        m, slots=sum(HETERO_CAPACITIES), chunk_sweeps=CHUNK, backend="jnp",
        V=V, rung="cb", telemetry=False, policy="backfill", **kw,
    )
    # Warmup pays jit for run(chunk) + splice/extract outside the timing.
    srv.submit(AnnealJob.constant(seed=1, sweeps=CHUNK, beta=1.0))
    srv.drain()
    spanning0 = srv._c_place_span.value
    best = None
    for _ in range(REPEATS):
        jobs = _hetero_jobs()
        sweeps0 = srv.stats()["sweeps_elapsed"]
        t0 = time.perf_counter()
        for j in jobs:
            srv.submit(j)
        by_jid = {r.jid: r for r in srv.drain()}
        dt = time.perf_counter() - t0
        sweeps = srv.stats()["sweeps_elapsed"] - sweeps0
        h = hashlib.sha256()
        for j in jobs:
            r = by_jid[j.jid]
            h.update(np.ascontiguousarray(r.spins).tobytes())
            h.update(np.asarray(r.energy, np.float64).tobytes())
        out = {
            "layout": layout,
            "slots": sum(HETERO_CAPACITIES),
            "num_jobs": len(jobs),
            "wall_s": dt,
            "sweeps_elapsed": int(sweeps),
            # jobs per global sweep: pure sweep-clock scheduling metric,
            # deterministic on any box (same reasoning as _sharded_section)
            "jobs_per_sweep": len(jobs) / sweeps,
            "jobs_per_sec": len(jobs) / dt,
            "spanning_placements": srv._c_place_span.value - spanning0,
            "spins_sha256": h.hexdigest(),
        }
        if best is None or dt < best["wall_s"]:
            best = out
    return best


def _hetero_mesh_section(rows, records):
    """Uneven [4,2,1,1] mesh vs the single-device engine, same 8 slots.

    The ISSUE 10 acceptance in bench form: a heterogeneous capacity
    vector is pure layout — per-job results are BIT-IDENTICAL to D=1
    (asserted via sha256, including the 6-replica ladder that must span
    devices on the ragged pool), and the sweep-clock drain schedule is
    unchanged (admission sees the same 8 global slots), so the gated
    ``jobs_per_sweep_vs_D1`` is deterministically 1.0 — any dip means
    the ragged layout perturbed admission timing.
    """
    if not _have_devices(len(HETERO_CAPACITIES), "serve_hetero_mesh", rows):
        return
    het = _serve_hetero("hetero")
    ref = _serve_hetero("d1")
    if het["spins_sha256"] != ref["spins_sha256"]:
        raise AssertionError(
            "hetero-mesh acceptance: [4,2,1,1] per-job results differ from "
            "the single-device engine (capacities must not change WHAT)"
        )
    if het["spanning_placements"] < 1:
        raise AssertionError(
            "hetero-mesh bench: the wide ladder never spanned devices — "
            "the mix no longer exercises the ragged spanning path"
        )
    ratio = het["jobs_per_sweep"] / ref["jobs_per_sweep"]
    rec = {
        "name": "serve_hetero_mesh",
        "B": het["slots"],
        "rung": "cb",
        "devices": len(HETERO_CAPACITIES),
        "capacities": list(HETERO_CAPACITIES),
        "num_jobs": het["num_jobs"],
        "wall_clock_s": het["wall_s"],
        "sweeps_per_sec": het["sweeps_elapsed"] / het["wall_s"],
        "jobs_per_sec": het["jobs_per_sec"],
        "jobs_per_sec_D1": ref["jobs_per_sec"],
        "sweeps_elapsed": het["sweeps_elapsed"],
        "sweeps_elapsed_D1": ref["sweeps_elapsed"],
        "jobs_per_sweep": het["jobs_per_sweep"],
        "jobs_per_sweep_vs_D1": ratio,
        "spanning_placements": het["spanning_placements"],
        "bit_identical_to_D1": True,
    }
    records.append(rec)
    rows.append(
        ("serve_hetero_mesh_jobs_per_sweep", het["jobs_per_sweep"] * 1e6,
         f"{het['num_jobs']} jobs in {het['sweeps_elapsed']} sweeps on "
         f"capacities {list(HETERO_CAPACITIES)} "
         f"({ratio:.2f}x the D=1 sweep clock, "
         f"{het['spanning_placements']} spanning placements, bit-identical)")
    )


def _telemetry_overhead_section(m, specs, rows, records):
    """Telemetry-on vs telemetry-off jobs/sec on the cb serving path.

    DESIGN.md §Observability promises <= 5% serving overhead with the
    full event pipeline on — measured here, never assumed: the SAME
    mixed workload through two resident servers, one with event
    recording on (spans, launch complete-events, per-launch
    block_until_ready timing), one with telemetry off (the
    pre-observability fire-and-forget hot path).  Rounds are INTERLEAVED
    (off, on, off, on, ...) so a slow patch on a shared box hits both
    sides alike; each side reports its best round.  The committed
    baseline's ``overhead_ratio`` (jobs/sec on / jobs/sec off) is gated
    by check_regression.py; the in-bench floor of 0.90 catches a gross
    regression even on a fresh machine with no baseline.
    """

    def make(flag: bool) -> SampleServer:
        srv = SampleServer(m, slots=8, chunk_sweeps=CHUNK, backend="jnp",
                           V=V, rung="cb", telemetry=flag)
        # Warmup pays jit for run(chunk)/splice/extract outside the timing.
        srv.submit(AnnealJob.constant(seed=1, sweeps=CHUNK, beta=1.0))
        srv.drain()
        return srv

    servers = {"off": make(False), "on": make(True)}
    best = {k: float("inf") for k in servers}
    res: dict[str, list] = {}
    # The gated overhead_ratio divides two ~0.3s walls whose honest gap
    # is a few percent, against ~10% per-round jitter on this shared
    # box — best-of-3 is not enough to resolve it (same reasoning as
    # SCHED_REPEATS).
    for _ in range(TELEMETRY_REPEATS):
        for k, srv in servers.items():
            jobs = [AnnealJob.constant(seed=s, sweeps=b, beta=be)
                    for s, b, be in specs]
            t0 = time.perf_counter()
            for j in jobs:
                srv.submit(j)
            by_jid = {r.jid: r for r in srv.drain()}
            best[k] = min(best[k], time.perf_counter() - t0)
            res[k] = [by_jid[j.jid] for j in jobs]
    # Observation must never change results, and events must have flowed.
    _check_bit_identical(res["off"], res["on"], specs, "telemetry_overhead")
    st_on = servers["on"].stats()["telemetry"]
    assert st_on["enabled"] and st_on["events_recorded"] > 0
    assert servers["off"].stats()["telemetry"]["events_recorded"] == 0
    total_sweeps = sum(b for _, b, _ in specs)
    n_spins = m.num_spins
    ratio = best["off"] / best["on"]  # == jobs/sec on / jobs/sec off
    if ratio < 0.90:
        raise AssertionError(
            f"telemetry overhead: jobs/sec with events on is {ratio:.3f}x "
            "the telemetry-off path (in-bench floor 0.90)"
        )
    for k in ("off", "on"):
        dt = best[k]
        rec = {
            "name": f"serve_telemetry_{k}",
            "B": 8,
            "rung": "cb",
            "telemetry": k == "on",
            "sweeps_per_sec": total_sweeps / dt,
            "wall_clock_s": dt,
            "jobs_per_sec": NUM_JOBS / dt,
            "spin_flips_per_sec": total_sweeps * n_spins / dt,
            "num_jobs": NUM_JOBS,
            "bit_identical_to_off": True,
        }
        if k == "on":
            rec["overhead_ratio"] = ratio
            rec["events_recorded"] = st_on["events_recorded"]
            rec["events_dropped"] = st_on["events_dropped"]
        records.append(rec)
        rows.append(
            (f"serve_telemetry_{k}_jobs_per_sec", NUM_JOBS / dt * 1e6,
             f"{NUM_JOBS / dt:.1f} jobs/s"
             + (f", {ratio:.3f}x of telemetry-off, "
                f"{st_on['events_recorded']} events" if k == "on" else ""))
        )


def _snapshot_overhead_section(m, specs, rows, records):
    """Periodic-snapshots-on vs snapshots-off jobs/sec on the cb path.

    DESIGN.md §Recovery promises that crash safety rides the background
    writer, not the hot path: the device->host pool extract happens at a
    step boundary and the npy/manifest I/O runs on a thread while serving
    continues.  Measured the same way as the telemetry claim: the SAME
    mixed workload through two resident servers, one snapshotting every
    ``SNAPSHOT_EVERY_SWEEPS`` of its sweep clock, one with snapshots off,
    rounds INTERLEAVED so shared-box noise hits both sides alike.

    A snapshot is not literally free: the consistency point is a step
    boundary, so each one pays a bounded device->host pool extract
    (sync + copy) before the writer thread takes over.  At THIS bench's
    toy scale (~0.1 s rounds, tiny lattice) that fixed cost reads as
    ~10%; on production lattices the same absolute cost vanishes into
    the chunk wall.  The committed baseline's ``overhead_ratio``
    (jobs/sec on / jobs/sec off) is gated by check_regression.py; the
    in-bench floor of 0.75 catches a gross regression (a snapshot gone
    blocking, an accidental per-chunk extract) even with no baseline.
    Bit-identity is asserted in-bench: snapshotting must never perturb
    results.
    """
    import tempfile

    with tempfile.TemporaryDirectory(prefix="serve_bench_snap_") as snap_dir:

        def make(flag: bool) -> SampleServer:
            srv = SampleServer(
                m, slots=8, chunk_sweeps=CHUNK, backend="jnp", V=V,
                rung="cb", telemetry=False,
                snapshot_manager=snap_dir if flag else None,
                snapshot_every_sweeps=SNAPSHOT_EVERY_SWEEPS if flag else 0,
            )
            # Warmup pays jit for run(chunk)/splice/extract outside the
            # timing.
            srv.submit(AnnealJob.constant(seed=1, sweeps=CHUNK, beta=1.0))
            srv.drain()
            return srv

        servers = {"off": make(False), "on": make(True)}
        best = {k: float("inf") for k in servers}
        res: dict[str, list] = {}
        for _ in range(TELEMETRY_REPEATS):  # same de-noising reasoning
            for k, srv in servers.items():
                jobs = [AnnealJob.constant(seed=s, sweeps=b, beta=be)
                        for s, b, be in specs]
                t0 = time.perf_counter()
                for j in jobs:
                    srv.submit(j)
                by_jid = {r.jid: r for r in srv.drain()}
                best[k] = min(best[k], time.perf_counter() - t0)
                res[k] = [by_jid[j.jid] for j in jobs]
        # Crash safety must never change results, and snapshots must
        # actually have been written (counters count even with telemetry
        # events off).
        _check_bit_identical(res["off"], res["on"], specs,
                             "snapshot_overhead")
        n_snaps = servers["on"].telemetry.counter("serve.snapshots").value
        assert n_snaps > 0, "snapshot-on server wrote no snapshots"
        assert servers["on"].snapshot_manager.valid_steps(), (
            "no valid snapshot on disk"
        )
        assert servers["off"].telemetry.counter("serve.snapshots").value == 0
    total_sweeps = sum(b for _, b, _ in specs)
    n_spins = m.num_spins
    ratio = best["off"] / best["on"]  # == jobs/sec on / jobs/sec off
    if ratio < 0.75:
        raise AssertionError(
            f"snapshot overhead: jobs/sec with periodic snapshots on is "
            f"{ratio:.3f}x the snapshots-off path (in-bench floor 0.75)"
        )
    for k in ("off", "on"):
        dt = best[k]
        rec = {
            "name": f"serve_snapshot_{k}",
            "B": 8,
            "rung": "cb",
            "snapshots": k == "on",
            "sweeps_per_sec": total_sweeps / dt,
            "wall_clock_s": dt,
            "jobs_per_sec": NUM_JOBS / dt,
            "spin_flips_per_sec": total_sweeps * n_spins / dt,
            "num_jobs": NUM_JOBS,
            "bit_identical_to_off": True,
        }
        if k == "on":
            rec["overhead_ratio"] = ratio
            rec["snapshots_written"] = int(n_snaps)
            rec["snapshot_every_sweeps"] = SNAPSHOT_EVERY_SWEEPS
        records.append(rec)
        rows.append(
            (f"serve_snapshot_{k}_jobs_per_sec", NUM_JOBS / dt * 1e6,
             f"{NUM_JOBS / dt:.1f} jobs/s"
             + (f", {ratio:.3f}x of snapshots-off, {int(n_snaps)} snapshots"
                if k == "on" else ""))
        )


URGENT_AT_SWEEPS = 40  # sweep-clock arrival of the urgent wide ladder


def sched_jobs(chunk: int) -> list:
    """The adversarial wide+narrow mix, fresh job objects per call.

    Submission order is the attack: three LONG narrow starters occupy
    slots, then a 6-slot PT ladder that cannot fit blocks the FIFO head
    while 5 slots idle for the 6 starter-chunks until the first starter
    retires, then a heavy user's narrow backlog (with a light user's
    jobs buried in it) queues up behind the blocker.  One extra URGENT
    (priority 2) wide ladder — jobs[-1] — is submitted mid-drain at
    sweep `URGENT_AT_SWEEPS`, when every slot is occupied: FIFO makes it
    wait for the whole backlog, the priority policies checkpoint-preempt
    running low-priority jobs for it.  Every budget is deterministic, so
    the reservation/backfill arithmetic — and the per-job results — are
    identical run to run.
    """
    jobs = [
        AnnealJob.constant(seed=500 + i, sweeps=(6 + 2 * i) * chunk, beta=1.0,
                           user="heavy")
        for i in range(3)
    ]
    jobs.append(
        PTJob(seed=600, betas=np.linspace(0.4, 1.4, 6).astype(np.float32),
              num_rounds=8, sweeps_per_round=chunk, user="batch")
    )
    rng = np.random.default_rng(99)
    for i in range(16):
        user = "light" if i % 4 == 3 else "heavy"
        jobs.append(
            AnnealJob.constant(
                seed=700 + i, sweeps=int(rng.integers(1, 4)) * chunk,
                beta=float(rng.uniform(0.5, 1.5)), user=user,
            )
        )
    jobs.append(
        PTJob(seed=800, betas=np.linspace(0.5, 1.5, 6).astype(np.float32),
              num_rounds=2, sweeps_per_round=chunk, user="urgent", priority=2)
    )
    return jobs


def make_sched_server(m, policy: str, chunk: int) -> SampleServer:
    srv = SampleServer(
        m, slots=SCHED_SLOTS, chunk_sweeps=chunk, backend="jnp", V=V,
        rung="cb", policy=policy, telemetry=False,
    )
    # Warmup covers run(chunk) plus the splice/extract/park jits.
    srv.submit(AnnealJob.constant(seed=1, sweeps=chunk, beta=1.0))
    srv.drain()
    return srv


def run_sched_round(srv: SampleServer, chunk: int):
    """One round of the sched mix through a resident server.  Returns
    (results by submission index, dt, per-job waits, stats deltas)."""
    # Fresh policy state per round (the fair policy's served-cost ledger
    # would otherwise carry over), so every round replays the IDENTICAL
    # schedule and differs only by clock noise.
    srv.policy = make_policy(srv.policy.name)
    base = srv.stats()
    jobs = sched_jobs(chunk)
    results = []
    t0 = time.perf_counter()
    for j in jobs[:-1]:
        srv.submit(j)
    # The urgent ladder arrives mid-drain, at a deterministic point
    # of the sweep clock, with every slot occupied.
    while srv.sweeps_elapsed - base["sweeps_elapsed"] < URGENT_AT_SWEEPS:
        results.extend(srv.step())
    srv.submit(jobs[-1])
    results.extend(srv.drain())
    dt = time.perf_counter() - t0
    st = srv.stats()
    by_jid = {r.jid: r for r in results}
    waits = np.array([j._admit_time - j._submit_time for j in jobs])
    # Sweep-clock waits are DETERMINISTIC (pure scheduling, no wall
    # noise): the acceptance assertions gate on these.
    wait_sweeps = np.array(
        [j._admit_sweep - j._submit_sweep for j in jobs], np.int64
    )
    round_stats = {
        "utilization": (
            (st["busy_slot_sweeps"] - base["busy_slot_sweeps"])
            / (st["total_slot_sweeps"] - base["total_slot_sweeps"])
        ),
        "busy_sweeps": st["busy_slot_sweeps"] - base["busy_slot_sweeps"],
        "sweeps_elapsed": st["sweeps_elapsed"] - base["sweeps_elapsed"],
        "launches": st["launches"] - base["launches"],
        "preemptions": st["preemptions"] - base["preemptions"],
        "urgent_wait_s": float(jobs[-1]._admit_time - jobs[-1]._submit_time),
        "urgent_wait_sweeps": int(wait_sweeps[-1]),
        "wait_sweeps": wait_sweeps,
    }
    return [by_jid[j.jid] for j in jobs], dt, waits, round_stats


def _sched_section(m, rows, records):
    """FIFO vs backfill vs fair on the adversarial mix (ISSUE 5).

    The three policies' rounds are INTERLEAVED (fifo, backfill, fair,
    fifo, ...) so a slow patch on a shared box hits every policy alike,
    and each policy reports its best round — determinism makes every
    round's results identical, so repetition only de-noises the clock.
    """
    servers = {p: make_sched_server(m, p, CHUNK) for p in SCHED_POLICIES}
    outs = {}
    all_waits = defaultdict(list)
    for _ in range(SCHED_REPEATS):
        for policy in SCHED_POLICIES:
            out = run_sched_round(servers[policy], CHUNK)
            all_waits[policy].append(out[2])
            if policy not in outs or out[1] < outs[policy][1]:
                outs[policy] = out
    ref_results = outs["fifo"][0]
    njobs = len(ref_results)
    metrics = {}
    for policy in SCHED_POLICIES:
        results, dt, _, st = outs[policy]
        # Every round runs the IDENTICAL deterministic schedule, so the
        # per-job wall waits differ between rounds only by clock noise:
        # de-noise with the elementwise min across rounds.
        waits = np.min(np.stack(all_waits[policy]), axis=0)
        # Scheduling changes WHEN, never WHAT: every job's spins must be
        # bit-identical to the FIFO run's.
        for i, (r_ref, r) in enumerate(zip(ref_results, results)):
            if not np.array_equal(r_ref.spins, r.spins):
                raise AssertionError(
                    f"sched policy={policy}: job {i} differs from FIFO run"
                )
        ws = st["wait_sweeps"]
        rec = {
            "name": f"sched_{policy}",
            "B": SCHED_SLOTS,
            "rung": "cb",
            "policy": policy,
            "sweeps_per_sec": st["busy_sweeps"] / dt,
            "wall_clock_s": dt,
            "jobs_per_sec": njobs / dt,
            "p50_wait_s": float(np.percentile(waits, 50)),
            "p95_wait_s": float(np.percentile(waits, 95)),
            "p50_wait_sweeps": float(np.percentile(ws, 50)),
            "p95_wait_sweeps": float(np.percentile(ws, 95)),
            "urgent_wait_s": float(waits[-1]),
            "urgent_wait_sweeps": st["urgent_wait_sweeps"],
            "sweeps_elapsed": st["sweeps_elapsed"],
            "utilization": st["utilization"],
            "launches": st["launches"],
            "preemptions": st["preemptions"],
            "num_jobs": njobs,
            "bit_identical_to_fifo": True,
        }
        if policy != "fifo":
            fifo = metrics["fifo"]
            rec["speedup_vs_fifo"] = fifo["wall_clock_s"] / dt
            rec["p95_wait_vs_fifo"] = rec["p95_wait_s"] / fifo["p95_wait_s"]
        metrics[policy] = rec
        records.append(rec)
        rows.append(
            (f"sched_{policy}_jobs_per_sec", njobs / dt * 1e6,
             f"{njobs / dt:.1f} jobs/s, p95 wait {rec['p95_wait_s']*1e3:.0f}ms "
             f"({rec['p95_wait_sweeps']:.0f} sweeps), "
             f"urgent {rec['urgent_wait_s']*1e3:.0f}ms, "
             f"util {rec['utilization']:.0%}, "
             f"{rec['preemptions']} preemptions")
        )
    # ISSUE 5 acceptance: backfill+fairness (the "fair" policy is the
    # full feature set) beats FIFO on jobs/sec AND p95 queue wait, with
    # bit-identical results (checked above).  Both new policies must
    # also win every DETERMINISTIC sweep-clock claim — fewer global
    # sweeps to drain the mix (higher utilization), lower p95 sweep
    # wait, near-zero urgent wait — which cannot flake on a noisy box.
    # Backfill-alone's wall p95 is NOT gated: its tail job admits at a
    # higher fraction of a much shorter drain, so the wall comparison
    # sits within box noise even though its sweep-clock p95 is strictly
    # better; its wall win is throughput.
    for policy in ("backfill", "fair"):
        rec, fifo = metrics[policy], metrics["fifo"]
        if rec["jobs_per_sec"] <= fifo["jobs_per_sec"]:
            raise AssertionError(
                f"sched acceptance: {policy} does not beat fifo on "
                f"throughput ({rec['jobs_per_sec']:.1f} vs "
                f"{fifo['jobs_per_sec']:.1f} jobs/s)"
            )
        assert rec["sweeps_elapsed"] < fifo["sweeps_elapsed"]
        assert rec["p95_wait_sweeps"] < fifo["p95_wait_sweeps"]
        assert rec["utilization"] > fifo["utilization"]
        assert rec["urgent_wait_sweeps"] < fifo["urgent_wait_sweeps"]
    fair, fifo = metrics["fair"], metrics["fifo"]
    if fair["p95_wait_s"] >= fifo["p95_wait_s"]:
        raise AssertionError(
            f"sched acceptance: fair does not beat fifo on p95 queue wait "
            f"({fair['p95_wait_s']:.3f}s vs {fifo['p95_wait_s']:.3f}s)"
        )


def run():
    m = ising.random_layered_model(n=MODEL_N, L=MODEL_L, seed=0, beta=1.0)
    specs = job_specs(NUM_JOBS, seed=42, chunk=CHUNK)
    rows, records = [], []

    # The paper-rung baseline comparison (unchanged from PR 2).
    _compare_section(m, specs, "serve", SLOT_CONFIGS, rung="a4",
                     rows=rows, records=records)

    # Colored rung: per-sweep cost is ~20x lower on the jnp path, so this
    # is the scheduler-overhead-honest measurement (ROADMAP item).
    _compare_section(m, specs, "serve_cb", SLOT_CONFIGS, rung="cb",
                     rows=rows, records=records)

    # Heterogeneous models: one lattice, NUM_TENANT_MODELS disorder
    # realizations, every job its own tenant — ISSUE 4 acceptance asks
    # packed >= 2x resident per-model sequential on this cb-jnp CPU path.
    tenants = [ising.reseed_couplings(m, seed=100 + k)
               for k in range(NUM_TENANT_MODELS)]
    _compare_section(m, specs, "serve_hetero", (8,), rung="cb",
                     models=tenants, rows=rows, records=records)

    # Telemetry overhead: the full event pipeline on vs off, same mix
    # (DESIGN.md §Observability's <= 5% claim, gated by check_regression).
    _telemetry_overhead_section(m, specs, rows, records)

    # Snapshot overhead: periodic background crash-safety snapshots on vs
    # off, same mix (DESIGN.md §Recovery's off-the-hot-path claim, gated
    # by check_regression).
    _snapshot_overhead_section(m, specs, rows, records)

    # Scheduling policies under the adversarial wide+narrow mix: FIFO vs
    # backfill vs fair (ISSUE 5 acceptance assertions inside).  Deeper
    # lattice so compute, not launch dispatch, dominates the wall clock.
    m_sched = ising.random_layered_model(
        n=MODEL_N, L=SCHED_MODEL_L, seed=0, beta=1.0
    )
    _sched_section(m_sched, rows, records)

    # Mesh-sharded slot pool at D in {1,2,4} devices, in this process
    # (hash-parity + sweep-clock scaling asserted inside).
    _sharded_section(rows, records)

    # Placement: device-affine vs flat free list on a PT-heavy mix at
    # D=4 (ISSUE 9 acceptance: fewer cross-device swap gathers, per-job
    # results bit-identical; cross_swap_ratio gated by check_regression).
    _placement_section(rows, records)

    # Heterogeneous mesh: uneven [4,2,1,1] capacities vs D=1, same global
    # slots (ISSUE 10 acceptance: bit-identical results incl. a spanning
    # ladder; jobs_per_sweep_vs_D1 gated by check_regression).
    _hetero_mesh_section(rows, records)

    path = write_bench_json("serve", records)
    rows.append(("serve_bench_json", 0.0, path))
    return rows


if __name__ == "__main__":
    for r in run():
        print(",".join(str(x) for x in r))
