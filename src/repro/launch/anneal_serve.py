"""Annealing-as-a-service CLI: a job mix through one resident SampleServer.

The Monte-Carlo sibling of `launch/serve.py`: instead of token slots it
packs annealing jobs (seed + beta schedule + sweep budget) and parallel-
tempering jobs (R slots each) into the replica batch of ONE resident
`SweepEngine`, advancing everyone by fused chunks and retiring/admitting
between chunks.

  PYTHONPATH=src python -m repro.launch.anneal_serve --smoke
  PYTHONPATH=src python -m repro.launch.anneal_serve \
      --jobs 32 --slots 8 --chunk 8 --backend jnp --n 8 --L 16

``--smoke`` is the CI gate: 8 mixed-budget jobs (constants, a ramp, and
a 3-replica PT job) on a tiny model, < 60 s on CPU.

The serving default rung is the graph-colored ``cb`` chain (same
equilibrium as a4, ~20x faster per sweep on the CPU jnp path — ROADMAP
colored-serving-default); ``--rung a4`` is the escape hatch back to the
paper's sequential order.  Admission defaults to the weighted-fair
priority scheduler (``--policy fair``: priority classes, backfill past
blocked wide jobs, per-user fairness, checkpoint-preemption — DESIGN.md
§Scheduling); ``--policy fifo`` restores the plain queue.  Results are
bit-identical under every policy — scheduling moves WHEN a job runs,
never what it computes.

``--devices D`` shards the slot pool over a D-device ("data",) mesh
(DESIGN.md §Mesh): slots must divide evenly and results stay bit-identical
to ``--devices 0`` (no mesh).  On a CPU-only host, force visible devices
first: ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.

``--trace PATH`` writes the run's Chrome-trace-event JSON (load it in
Perfetto / chrome://tracing: job lifecycle tracks, engine launches with
compile-vs-steady, scheduler decisions); ``--metrics`` prints the
Prometheus text exposition of the server's metric registry after the
drain (DESIGN.md §Observability).  ``--smoke`` exercises both.

CRASH SAFETY (DESIGN.md §Recovery): ``--snapshot-dir`` arms whole-server
snapshots — ``--snapshot-every K`` writes one every K sweeps off the hot
path, and SIGTERM triggers a graceful drain (finish the in-flight chunk,
snapshot, exit 0).  ``--resume`` restores the newest valid snapshot from
the directory and finishes its recorded jobs instead of submitting a
fresh mix; results are bit-identical to the uninterrupted run.
``--smoke`` exercises the full cycle: serve with periodic snapshots,
simulate a kill mid-drain, restore, finish, and check every job landed.
"""

from __future__ import annotations

import argparse
import tempfile
import time

import numpy as np

from repro.core import ising
from repro.launch.compile_cache import enable_compile_cache
from repro.serve_mc import AnnealJob, PTJob, SampleServer


def build_job_mix(args) -> list:
    """A deterministic mixed workload: mostly constant-beta jobs with
    scattered budgets, every 4th job an anneal ramp, plus one PT job when
    ``--pt-replicas`` > 0."""
    rng = np.random.default_rng(args.seed)
    jobs = []
    for i in range(args.jobs):
        budget = int(rng.integers(args.budget_min, args.budget_max + 1))
        user = f"user{i % 3}"  # three tenants sharing the server
        priority = 1 if i % 5 == 4 else 0  # every 5th job is expedited
        if i % 4 == 3:
            steps = max(2, budget // max(1, args.chunk))
            jobs.append(
                AnnealJob.ramp(
                    seed=args.seed * 1000 + i,
                    beta_start=0.3,
                    beta_end=float(args.beta),
                    steps=steps,
                    sweeps_per_step=max(1, budget // steps),
                    user=user,
                    priority=priority,
                )
            )
        else:
            jobs.append(
                AnnealJob.constant(
                    seed=args.seed * 1000 + i,
                    sweeps=budget,
                    beta=float(rng.uniform(0.5, 1.5)),
                    user=user,
                    priority=priority,
                )
            )
    if args.pt_replicas > 0:
        betas = np.linspace(0.4, args.beta, args.pt_replicas).astype(np.float32)
        jobs.append(
            PTJob(
                seed=args.seed + 77,
                betas=betas,
                num_rounds=args.pt_rounds,
                sweeps_per_round=max(1, args.chunk // 2),
                user="ladder",
                priority=1,  # the wide job: exercises preemption/backfill
            )
        )
    return jobs


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: 8 mixed jobs incl. ramp + PT, <60s CPU")
    ap.add_argument("--jobs", type=int, default=16)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument("--backend", default="jnp", choices=["jnp", "pallas"])
    ap.add_argument("--rung", default="cb",
                    help="sweep rung; the colored 'cb' chain is the serving "
                         "default, --rung a4 restores sequential order")
    ap.add_argument("--policy", default="fair",
                    choices=["fifo", "backfill", "fair"],
                    help="admission policy; weighted-fair priority "
                         "scheduling is the serving default, --policy fifo "
                         "restores the plain queue (results are identical)")
    ap.add_argument("--devices", type=int, default=0,
                    help="shard the slot pool over this many devices on a "
                         "('data',) mesh; 0 = single-device (no mesh). "
                         "Results are bit-identical either way.")
    ap.add_argument("--V", type=int, default=4)
    ap.add_argument("--n", type=int, default=8)
    ap.add_argument("--L", type=int, default=16)
    ap.add_argument("--beta", type=float, default=1.2)
    ap.add_argument("--budget-min", type=int, default=8)
    ap.add_argument("--budget-max", type=int, default=32)
    ap.add_argument("--pt-replicas", type=int, default=0)
    ap.add_argument("--pt-rounds", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="write a Chrome-trace-event JSON of the run "
                         "(Perfetto / chrome://tracing loadable)")
    ap.add_argument("--metrics", action="store_true",
                    help="print the Prometheus text exposition of the "
                         "server's metric registry after the drain")
    ap.add_argument("--snapshot-dir", metavar="DIR", default=None,
                    help="arm crash safety: periodic snapshots land here and "
                         "SIGTERM drains gracefully (finish chunk, snapshot, "
                         "exit 0)")
    ap.add_argument("--snapshot-every", type=int, default=0, metavar="K",
                    help="write a background snapshot every K sweeps "
                         "(0 = only on SIGTERM; needs --snapshot-dir)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest valid snapshot from "
                         "--snapshot-dir and finish its recorded jobs "
                         "instead of submitting a fresh mix")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.smoke:
        # 7 anneal jobs + 1 three-replica PT job = 8 jobs on 4 slots.
        args.jobs, args.slots, args.chunk = 7, 4, 4
        args.n, args.L, args.V = 8, 16, 4
        args.budget_min, args.budget_max = 4, 24
        args.pt_replicas, args.pt_rounds = 3, 3
        args.backend = "jnp"
        if args.trace is None:
            args.trace = "serve_smoke_trace.json"
        args.metrics = True
        if args.snapshot_every == 0:
            args.snapshot_every = 16  # force >=1 periodic snapshot pre-"crash"

    if args.resume and args.snapshot_dir is None:
        ap.error("--resume needs --snapshot-dir")
    if args.snapshot_every and args.snapshot_dir is None and not args.smoke:
        ap.error("--snapshot-every needs --snapshot-dir")

    mesh = None
    if args.devices > 0:
        from repro.launch.mesh import make_slot_mesh

        mesh = make_slot_mesh(args.devices)

    snap_tmp = None
    snap_dir = args.snapshot_dir
    if args.smoke and snap_dir is None:
        snap_tmp = tempfile.TemporaryDirectory(prefix="serve_smoke_snap_")
        snap_dir = snap_tmp.name

    preemption = None
    if snap_dir is not None:
        from repro.runtime.ft import PreemptionHandler

        preemption = PreemptionHandler()  # SIGTERM -> graceful drain

    if args.resume:
        server = SampleServer.restore(
            snap_dir,
            mesh=mesh,
            snapshot_every_sweeps=args.snapshot_every or None,
            preemption=preemption,
        )
        jobs = []  # the snapshot's recorded jobs are the workload
        print(
            f"resumed from {snap_dir} at {server.sweeps_elapsed} sweeps "
            f"({len(server.policy)} queued, {len(server._active)} active, "
            f"{len(server._retired)} already retired)"
        )
    else:
        model = ising.random_layered_model(
            n=args.n, L=args.L, seed=args.seed, beta=args.beta
        )
        server = SampleServer(
            model,
            slots=args.slots,
            chunk_sweeps=args.chunk,
            rung=args.rung,
            backend=args.backend,
            V=args.V,
            policy=args.policy,
            mesh=mesh,
            snapshot_manager=snap_dir,
            snapshot_every_sweeps=args.snapshot_every if snap_dir else 0,
            preemption=preemption,
        )
        jobs = build_job_mix(args)
        for job in jobs:
            server.submit(job)
        dev = f", mesh={args.devices} devices" if mesh is not None else ""
        snp = f", snapshots every {args.snapshot_every} sweeps -> {snap_dir}" \
            if snap_dir else ""
        print(
            f"serving {len(jobs)} jobs on {args.slots} slots "
            f"(chunk={args.chunk} sweeps, backend={args.backend}, "
            f"policy={args.policy}, model n={args.n} L={args.L}{dev}{snp})"
        )

    t0 = time.perf_counter()
    if args.smoke and not args.resume:
        # save -> kill -> resume, end to end: serve until at least one
        # periodic snapshot has landed and one job retired, then abandon
        # the server (a stand-in for SIGKILL: no goodbye snapshot) and
        # restore from the last periodic snapshot to finish the drain.
        pre = []
        while len(server.policy) or server._active:
            pre.extend(server.step())
            server.wait_snapshots()
            if server.snapshot_manager.latest_step() is not None and pre:
                break
        crash_sweeps = server.sweeps_elapsed
        snap_step = server.snapshot_manager.latest_step()
        results = pre
        if len(server.policy) or server._active:
            print(
                f"smoke: simulated crash at {crash_sweeps} sweeps "
                f"({len(pre)} jobs already retired, last snapshot at "
                f"sweep {snap_step})"
            )
            del server  # the "kill": in-flight state is gone
            server = SampleServer.restore(snap_dir, mesh=mesh)
            post = server.drain()
            # Jobs retired between the snapshot and the crash are re-run
            # by the restored server; keep one result per jid (they are
            # bit-identical — determinism contract).
            by_jid = {r.jid: r for r in pre}
            by_jid.update({r.jid: r for r in post})
            results = [by_jid[j] for j in sorted(by_jid)]
            print(
                f"smoke: resumed from snapshot, {len(post)} jobs finished "
                f"after restore"
            )
    else:
        results = server.drain()
    dt = time.perf_counter() - t0
    if server.preempted:
        step = server.snapshot_manager.latest_step()
        print(
            f"preempted: drained gracefully after {len(results)} jobs, "
            f"snapshot at step {step} in {snap_dir} (resume with --resume)"
        )
        if snap_tmp is not None:
            snap_tmp.cleanup()
        return results

    for r in sorted(results, key=lambda r: r.jid)[:8]:
        e = r.energy if np.ndim(r.energy) == 0 else float(np.min(r.energy))
        kind = "pt" if np.ndim(r.spins) == 2 else "anneal"
        print(
            f"  job {r.jid:3d} [{kind}] {r.sweeps_done:4d} sweeps in "
            f"{r.chunks:3d} chunks  E={e:9.2f}  m={np.mean(r.magnetization):+.3f}"
        )
    st = server.stats()
    jobs_per_sec = len(results) / dt
    flips_per_sec = st["spin_flips"] / dt
    print(
        f"served {len(results)} jobs in {dt:.2f}s: {jobs_per_sec:.1f} jobs/s, "
        f"{st['busy_slot_sweeps'] / dt:.0f} sweeps/s, "
        f"{flips_per_sec / 1e6:.2f}M spin-flips/s, "
        f"{st['launches']} launches, utilization {st['utilization']:.0%} "
        f"({st['useful_slot_sweeps']} useful / "
        f"{st['idle_resweep_slot_sweeps']} idle-resweep slot-sweeps), "
        f"{st['preemptions']} preemptions"
    )
    qw = st["queue_wait"]
    if qw["overall"]["count"]:
        print(
            f"queue wait p50={qw['overall']['p50_s'] * 1e3:.0f}ms "
            f"p95={qw['overall']['p95_s'] * 1e3:.0f}ms; per-user p95: "
            + ", ".join(
                f"{u}={agg['p95_s'] * 1e3:.0f}ms"
                for u, agg in sorted(qw["by_user"].items())
            )
        )
    recent = st["queue_wait_recent"]
    if recent["count"]:
        print(
            f"recent queue wait (last {recent['count']} of window "
            f"{recent['window']} admissions): "
            f"p50={recent['p50_s'] * 1e3:.0f}ms "
            f"p95={recent['p95_s'] * 1e3:.0f}ms "
            f"({recent['p50_sweeps']:.0f}/{recent['p95_sweeps']:.0f} sweeps)"
        )
    if args.trace:
        from repro.obs.trace import validate_events

        path = server.telemetry.write_chrome_trace(args.trace)
        trace = server.telemetry.chrome_trace()
        validate_events(trace["traceEvents"])  # a broken trace fails the run
        tel = st["telemetry"]
        print(
            f"trace: {len(trace['traceEvents'])} events -> {path} "
            f"({tel['events_dropped']} dropped by the ring)"
        )
    if args.metrics:
        print("-- metrics (Prometheus text exposition) --")
        print(server.telemetry.prometheus_text(), end="")
    if snap_tmp is not None:
        server.wait_snapshots()
        snap_tmp.cleanup()
    if jobs and len(results) != len(jobs):
        raise RuntimeError(f"served {len(results)} of {len(jobs)} jobs")
    return results


if __name__ == "__main__":
    main()
