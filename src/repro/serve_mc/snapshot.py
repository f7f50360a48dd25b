"""Whole-server snapshot/restore for `SampleServer` (DESIGN.md §Recovery).

A snapshot is the COMPLETE resumable state of a serving process, taken
between scheduling rounds (the same chunk-boundary consistency point that
makes admission and preemption safe):

* the whole slot pool in GLOBAL layout (`SweepEngine.extract_pool`):
  every slot's spins/fields/betas and the interlaced MT19937 generator
  columns at their exact stream positions — including idle slots' stale
  state, whose resweeps are part of the pool's deterministic trajectory —
  plus the batched per-slot coupling tables on multi-tenant engines;
* every job, queued or active: segment progress, scheduler stamps,
  parked-slot carries from earlier preemptions, PT swap RNG/tallies, and
  any job-private model (serialized field by field — `LayeredModel` is
  plain numpy + scalars, so the round-trip is exact);
* the admission policy's internals: queue order (submission seqs), the
  fair policy's served-cost ledger, aging clock, and construction config;
* the server's accounting: telemetry counters, per-chunk launch series,
  adaptive-chunker EWMA, wait-stat rings, free list, next job id, and
  the retirement log.

Everything lands in ONE flat ``{name: ndarray}`` dict plus a JSON-safe
manifest ``extra``, written through `ckpt.manager.CheckpointManager.
save_named` (atomic tmp+rename, per-shard sha256, async writer) — so a
snapshot needs no like-tree to read back: the restorer learns the job and
slot layout FROM the checkpoint.

Restore (`restore_server`) rebuilds the server from the recorded config
(constructor arguments are overridable — notably ``mesh``: carries are
stored de-sharded in global layout, so restoring a D=4 snapshot on D=1,
or the reverse, is just a `device_put` against the new mesh) and
continues BIT-EXACTLY equal to an uninterrupted run: spins, energies,
raw RNG, and retirement order (tests/test_snapshot.py).  The only
intentionally unrestored state is wall-clock-derived: jit warm caches
(a new process recompiles; the first launches correctly trace
``compile=True``), wall-second wait stamps (sweep-clock waits are exact),
and telemetry *event* rings (counters ARE restored — `stats()` is built
on them).
"""

from __future__ import annotations

import dataclasses
import numbers

import jax.numpy as jnp
import numpy as np

from repro.ckpt.manager import CheckpointManager
from repro.core import ising
from repro.core.engine import PoolState, SweepCarry

#: Bumped on any incompatible change to the layout below; restore refuses
#: a snapshot whose version it does not understand (failing loudly beats
#: resuming from misread state).
SNAPSHOT_VERSION = 1


# -----------------------------------------------------------------------------
# LayeredModel <-> (meta, arrays): field-by-field, exact.
# -----------------------------------------------------------------------------


def _model_state(model: ising.LayeredModel, arrays: dict, prefix: str) -> dict:
    """Serialize ``model`` into ``arrays[prefix/...]``; returns its meta."""
    meta = {}
    for f in dataclasses.fields(model):
        v = getattr(model, f.name)
        if isinstance(v, np.ndarray):
            arrays[f"{prefix}/{f.name}"] = v
        elif isinstance(v, numbers.Number):
            meta[f.name] = v
        else:
            raise TypeError(
                f"cannot snapshot model field {f.name!r} of type {type(v)}"
            )
    return meta


def _model_from(meta: dict, arrays: dict, prefix: str) -> ising.LayeredModel:
    kwargs = dict(meta)
    for f in dataclasses.fields(ising.LayeredModel):
        key = f"{prefix}/{f.name}"
        if key in arrays:
            kwargs[f.name] = arrays[key]
    return ising.LayeredModel(**kwargs)


# -----------------------------------------------------------------------------
# Policy <-> meta.
# -----------------------------------------------------------------------------


def _policy_state(policy) -> dict:
    from repro.serve_mc.scheduler import AdmissionPolicy, PriorityBackfillPolicy

    meta = {"name": policy.name, "seq": policy._seq, "clock": policy.clock}
    if isinstance(policy, PriorityBackfillPolicy):
        meta.update(
            backfill=policy.backfill,
            preempt=policy.preempt,
            fair=policy.fair,
            user_weights=dict(policy.user_weights),
            aging_sweeps=policy.aging_sweeps,
            served={u: float(v) for u, v in policy._served.items()},
        )
    elif type(policy) is not AdmissionPolicy:
        raise TypeError(
            f"cannot snapshot custom admission policy {type(policy).__name__}; "
            "snapshots support the built-in fifo/backfill/fair policies"
        )
    return meta


def _policy_from(meta: dict):
    from repro.serve_mc.scheduler import AdmissionPolicy, PriorityBackfillPolicy

    if "fair" in meta:
        return PriorityBackfillPolicy(
            backfill=meta["backfill"],
            preempt=meta["preempt"],
            fair=meta["fair"],
            user_weights=meta["user_weights"],
            aging_sweeps=meta["aging_sweeps"],
        )
    return AdmissionPolicy()


# -----------------------------------------------------------------------------
# Snapshot a live server.
# -----------------------------------------------------------------------------


def snapshot_state(server) -> tuple[dict, dict]:
    """``(arrays, extra)`` capturing ``server`` completely.

    Arrays are host numpy in global layout (the pool is de-sharded once);
    ``extra`` is JSON-safe.  Pure read — the server is untouched, so the
    caller may keep stepping it (periodic snapshots hand the arrays to
    the manager's background writer; nothing here is mutated in place by
    later steps, only rebound).
    """
    from repro.serve_mc.jobs import _ScheduledJob  # noqa: F401  (doc link)

    eng = server.engine
    arrays: dict = {}
    pool = eng.extract_pool(server.carry)
    for name, v in zip(SweepCarry._fields, pool.carry):
        arrays[f"carry/{name}"] = v
    if pool.tables is not None:
        for k, v in pool.tables.items():
            arrays[f"tables/{k}"] = v
    model_meta = _model_state(eng.model, arrays, "base_model")

    jobs_meta = []

    def add_job(job, role, slots=None):
        key = f"job/{job.jid}"
        meta, jarrays = job.snapshot_state()
        for k, v in jarrays.items():
            arrays[f"{key}/{k}"] = v
        if job.model is not None:
            meta["model"] = _model_state(job.model, arrays, f"{key}/model")
        entry = {"role": role, "meta": meta}
        if slots is not None:
            entry["slots"] = [int(b) for b in slots]
        jobs_meta.append(entry)

    for job in server.policy.jobs():  # queue order == restore enqueue order
        add_job(job, "queued")
    for jid, (job, slots) in server._active.items():
        add_job(job, "active", slots)

    chunker = None
    if server._chunker is not None:
        ck = server._chunker
        chunker = {
            "target_launch_s": ck.target_launch_s,
            "max_chunk": ck.menu[-1],
            "init_chunk": ck.init_chunk,
            "alpha": ck.alpha,
            "per_sweep_ewma": ck.per_sweep_ewma,
        }

    extra = {
        "version": SNAPSHOT_VERSION,
        "config": {
            "slots": server.slots,
            "chunk_sweeps": (
                "adaptive" if server._chunker is not None else server.chunk_sweeps
            ),
            "rung": eng.rung,
            "backend": eng.backend,
            "V": eng.V,
            "exp_flavor": eng.exp_flavor,
            "interpret": eng.interpret,
            "replica_tile": eng.replica_tile,
            "multi_tenant": server.multi_tenant,
            "wait_window": server._wait_recent.maxlen,
            "devices": server.devices,
            "placement": server._pool.mode,
            # Capacity vector the snapshot was taken under (None for the
            # equal split).  Informational for the restorer: the pool is
            # stored in LOGICAL layout, so restoring onto a DIFFERENT
            # vector — or none — is just a re-layout, not a migration.
            "capacities": (
                list(server.config.capacities)
                if server.config.capacities is not None
                else None
            ),
            "snapshot_every_sweeps": server.snapshot_every_sweeps,
        },
        "model": model_meta,
        "policy": _policy_state(server.policy),
        "jobs": jobs_meta,
        # The free list is stored FLAT in global slot indices: the
        # per-device keying is a pure function of (index, capacity
        # vector), so the restoring server rebuilds its own pool for ITS
        # mesh — a D=4 snapshot restores onto D=1, an uneven vector onto
        # an even one, and vice versa, with placement state intact (the
        # same slots are free; only the keying moves).
        "free": [int(b) for b in server._pool.flat_free()],
        "free_by_device": server._pool.free_by_device(),  # informational
        "next_jid": server._next_jid,
        "counters": {
            "launches": server.launches,
            "sweeps_elapsed": server.sweeps_elapsed,
            "busy_slot_sweeps": server.busy_slot_sweeps,
            "total_slot_sweeps": server.total_slot_sweeps,
            "preemptions": server.preemptions,
            "submitted": server._c_submitted.value,
            "completed": server._c_completed.value,
            "straggler": server._c_straggler.value,
            "placements_affine": server._c_place_affine.value,
            "placements_spanning": server._c_place_span.value,
            "rebalance_migrations": server._c_migrations.value,
            "pt_swap_local": server._c_swap_local.value,
            "pt_swap_cross": server._c_swap_cross.value,
        },
        "launch_chunks": {
            str(k): int(v) for k, v in server.launch_chunks.items()
        },
        "chunker": chunker,
        "wait_records": [list(r) for r in server._wait_records],
        "wait_recent": [list(r) for r in server._wait_recent],
        "retired": [int(j) for j in server._retired],
    }
    return arrays, extra


def save_snapshot(server, manager: CheckpointManager, *, step=None,
                  blocking: bool = True) -> int:
    """Snapshot ``server`` at ``step`` (default: its sweep clock).

    The `snapshot.save` span covers the synchronous part only — the pool
    gather and manifest build; with ``blocking=False`` the disk writes
    (fsync'd npy shards + manifest, then the atomic rename) happen on the
    manager's background thread, off the serving hot path.
    """
    step = int(server.sweeps_elapsed if step is None else step)
    tel = server.telemetry
    with tel.span("snapshot.save", step=step, blocking=blocking):
        arrays, extra = snapshot_state(server)
        manager.save_named(step, arrays, blocking=blocking, extra=extra)
        tel.counter("serve.snapshots").add(1)
    return step


# -----------------------------------------------------------------------------
# Restore.
# -----------------------------------------------------------------------------


def _sub_arrays(arrays: dict, prefix: str) -> dict:
    p = prefix + "/"
    return {k[len(p):]: v for k, v in arrays.items() if k.startswith(p)}


def restore_server(
    source,
    *,
    step: int | None = None,
    mesh=None,
    capacities=None,
    backend: str | None = None,
    interpret: bool | None = None,
    replica_tile: int | None = None,
    chunk_sweeps=None,
    placement: str | None = None,
    telemetry=True,
    stream=None,
    snapshot_manager=None,
    snapshot_every_sweeps: int | None = None,
    preemption=None,
):
    """Rebuild a `SampleServer` from a snapshot and continue bit-exactly.

    ``source`` is a `CheckpointManager` or a snapshot directory path;
    ``step=None`` restores the newest VALID snapshot (corrupt ones are
    skipped and GC'd by the manager).  Keyword overrides replace the
    recorded construction parameters — ``mesh`` is the usual one: the
    pool is stored in LOGICAL global layout, so a D=4 snapshot restores
    onto D=1 (mesh=None) or any other mesh, and vice versa.
    ``capacities`` pairs with ``mesh`` the same way it does at
    construction: a snapshot taken under one capacity vector restores
    bit-exactly onto any other (or onto the default equal split) — the
    recorded vector is informational (``extra["config"]["capacities"]``),
    never implicitly reapplied, since the restoring mesh may have a
    different device count entirely.  By
    default periodic snapshots continue into ``source`` at the recorded
    cadence; pass ``snapshot_manager``/``snapshot_every_sweeps`` to
    redirect or disable them.
    """
    from repro.serve_mc.jobs import AnnealJob, PTJob, SQAJob
    from repro.serve_mc.scheduler import AdaptiveChunker, SampleServer

    mgr = source if isinstance(source, CheckpointManager) else CheckpointManager(str(source))
    if step is None:
        step, arrays, extra = mgr.restore_latest_named()
        if step is None:
            raise FileNotFoundError(
                f"no valid snapshot found under {mgr.dir!r}"
            )
    else:
        arrays, extra = mgr.restore_named(step)
    version = extra.get("version")
    if version != SNAPSHOT_VERSION:
        raise ValueError(
            f"snapshot version {version!r} != supported {SNAPSHOT_VERSION}"
        )

    cfg = extra["config"]
    base_model = _model_from(extra["model"], arrays, "base_model")
    policy = _policy_from(extra["policy"])

    cs = cfg["chunk_sweeps"] if chunk_sweeps is None else chunk_sweeps
    chunker = None
    if cs == "adaptive":
        ck = extra.get("chunker") or {}
        chunker = AdaptiveChunker(
            target_launch_s=ck.get("target_launch_s", 0.05),
            max_chunk=ck.get("max_chunk", 64),
            init_chunk=ck.get("init_chunk", 8),
            alpha=ck.get("alpha", 0.3),
        )
        # Resume the measured launch-cost EWMA; the warm set is NOT
        # restored — a fresh process recompiles, and `observe` must keep
        # discarding each size's first (compile) launch.
        chunker.per_sweep_ewma = ck.get("per_sweep_ewma")

    server = SampleServer(
        base_model,
        slots=cfg["slots"],
        chunk_sweeps=cs,
        rung=cfg["rung"],
        backend=cfg["backend"] if backend is None else backend,
        V=cfg["V"],
        exp_flavor=cfg["exp_flavor"],
        interpret=cfg["interpret"] if interpret is None else interpret,
        replica_tile=(
            cfg["replica_tile"] if replica_tile is None else replica_tile
        ),
        chunker=chunker,
        multi_tenant=cfg["multi_tenant"],
        policy=policy,
        wait_window=cfg["wait_window"],
        mesh=mesh,
        capacities=capacities,
        placement=(
            cfg.get("placement", "affine") if placement is None else placement
        ),
        telemetry=telemetry,
        stream=stream,
        snapshot_manager=mgr if snapshot_manager is None else snapshot_manager,
        snapshot_every_sweeps=(
            cfg.get("snapshot_every_sweeps", 0)
            if snapshot_every_sweeps is None
            else snapshot_every_sweeps
        ),
        preemption=preemption,
    )

    # Pool: global-layout host arrays -> this server's mesh (device_put).
    tables = _sub_arrays(arrays, "tables") or None
    pool = PoolState(
        SweepCarry(*(arrays[f"carry/{n}"] for n in SweepCarry._fields)),
        tables,
    )
    server.carry = server.engine.splice_pool(pool)

    # Jobs: queued (in recorded queue order) then active.
    kinds = {"anneal": AnnealJob, "pt": PTJob, "sqa": SQAJob}
    for entry in extra["jobs"]:
        meta = entry["meta"]
        key = f"job/{meta['jid']}"
        model = (
            _model_from(meta["model"], arrays, f"{key}/model")
            if "model" in meta
            else None
        )
        job = kinds[meta["kind"]].from_snapshot(
            meta, _sub_arrays(arrays, key), model=model
        )
        if entry["role"] == "queued":
            server.policy.enqueue(job)
        else:
            server._active[job.jid] = (job, tuple(entry["slots"]))
        server.telemetry.async_begin(
            "job",
            job.jid,
            kind=job.kind,
            slots=job.num_slots,
            priority=job.priority,
            user=job.user,
            restored=True,
        )
    # The ledger/seq/clock go in AFTER enqueues: enqueue's entering-the-
    # backlog flooring must not perturb the restored served levels.
    pol_meta = extra["policy"]
    if "served" in pol_meta:
        server.policy._served = {
            u: float(v) for u, v in pol_meta["served"].items()
        }
    server.policy._seq = pol_meta["seq"]
    server.policy.clock = pol_meta["clock"]

    # Rebuild the free pool from the flat global list: per-device keying
    # is recomputed for THIS server's device count (D may have changed).
    server._pool.restore_free(extra["free"])
    server._next_jid = int(extra["next_jid"])

    c = extra["counters"]
    server._c_launches.add(c["launches"])
    server._c_sweeps.add(c["sweeps_elapsed"])
    server._c_busy.add(c["busy_slot_sweeps"])
    server._c_total.add(c["total_slot_sweeps"])
    server._c_preempt.add(c["preemptions"])
    server._c_submitted.add(c["submitted"])
    server._c_completed.add(c["completed"])
    server._c_straggler.add(c["straggler"])
    server._c_place_affine.add(c.get("placements_affine", 0))
    server._c_place_span.add(c.get("placements_spanning", 0))
    server._c_migrations.add(c.get("rebalance_migrations", 0))
    server._c_swap_local.add(c.get("pt_swap_local", 0))
    server._c_swap_cross.add(c.get("pt_swap_cross", 0))
    for chunk, v in extra["launch_chunks"].items():
        server.telemetry.counter(
            "serve.launches_by_chunk", chunk=int(chunk)
        ).add(int(v))
    server._wait_records.extend(tuple(r) for r in extra["wait_records"])
    server._wait_recent.extend(tuple(r) for r in extra["wait_recent"])
    server._retired.extend(int(j) for j in extra["retired"])
    server._last_snapshot_sweep = server.sweeps_elapsed
    server.telemetry.instant(
        "snapshot.restore",
        step=step,
        devices=server.devices,
        saved_devices=cfg["devices"],
        queued=len(server.policy),
        active=len(server._active),
    )
    return server
