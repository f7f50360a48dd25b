"""Job types the `SampleServer` schedules onto engine slots.

A job is a unit of sampling work that occupies ``num_slots`` slots of the
server's resident `SweepEngine` batch from admission to retirement.  Its
lifetime is expressed in *segments*: maximal runs of sweeps during which
the job's betas are constant and no job-private bookkeeping is needed.
The scheduler may cut a segment into several fused-launch chunks (chunk
boundaries never change results — the RNG stream position is a pure
function of sweeps completed), but it always stops exactly at segment
boundaries, where the job's ``on_segment`` hook runs:

  * `AnnealJob`   — one slot; a piecewise-constant anneal schedule.  The
    hook hands the next segment's beta to the server, which rewrites
    every due slot's beta in one engine call after the step's hooks.
  * `SQAJob`      — one slot; simulated quantum annealing, a transverse
    field ramp at fixed beta.  The hook hands the next Gamma's tau
    couplings to the server, which rewrites every due slot's tau row in
    one engine call after the step's hooks (multi-tenant servers only).
  * `PTJob`       — R slots; every segment is one parallel-tempering
    round.  The hook is one `tempering.swap_slots` call over the shared
    carry: it reads only the job's spins and betas and writes the swapped
    betas back, so a tempering round is literally "one scheduled chunk +
    swap" and shares fused launches with whatever else is resident.

Every job type reproduces its standalone counterpart bit for bit: an
`AnnealJob` equals a solo ``SweepEngine`` run with the same seed and
schedule, a `PTJob` equals `tempering.run_parallel_tempering`, an `SQAJob`
a solo engine swept under each Gamma's model in turn — no matter which
slots they land in or what runs beside them (tests/test_serve_mc.py,
tests/test_sqa.py).

On a MULTI-TENANT server (``SampleServer(..., multi_tenant=True)``) either
job may additionally carry its OWN `LayeredModel` (same lattice topology
as the server's base model): admission splices the model's coupling tables
into the job's slots next to the carry, so jobs over different spin-glass
instances ride the same fused launches — and still reproduce their solo
runs bit for bit (DESIGN.md §Multi-tenancy).
"""

from __future__ import annotations

import time
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import engine as sweep_engine
from repro.core import ising, mt19937, observables, qmc, tempering


class JobResult(NamedTuple):
    """What a retired job hands back to the submitter."""

    jid: int
    spins: np.ndarray  # (N,) flat layer-major; (R, N) for multi-slot jobs
    energy: float | np.ndarray
    magnetization: float | np.ndarray
    sweeps_done: int
    chunks: int  # fused launches this job rode in
    extras: dict


class _ScheduledJob:
    """Segment bookkeeping shared by every job type.

    ``segments`` is a list of positive sweep counts.  The scheduler only
    ever advances a job by ``k <= remaining_in_segment()`` sweeps.

    ``model`` is the job's OWN `LayeredModel` (multi-tenant servers only):
    admission splices its coupling tables into the job's slots alongside
    the carry, so jobs over *different* models of one lattice share fused
    launches.  ``model=None`` means the server's base model — the only
    option on a single-model server.

    ``priority`` and ``user`` feed the server's admission policy
    (DESIGN.md §Scheduling): higher priority admits first (strict tiers;
    0 is the default class), and under the fair policy jobs compete for
    slots per-``user`` (weighted fair ordering), not globally.  Neither
    affects results — scheduling changes WHEN a job runs, never what it
    computes (the slot-privacy determinism contract).

    ``parked`` is the job's checkpoint state after a preemption: the list
    of `engine.ParkedSlot`s (one per occupied slot, in replica order)
    extracted at the chunk boundary it was evicted on.  Re-admission
    splices them back instead of calling `init_carries`, resuming the
    trajectory bit-exactly; segment bookkeeping (`sweeps_done`,
    ``remaining_in_segment``) simply continues from where it stopped.
    """

    num_slots = 1
    kind = "job"  # telemetry label (job lifecycle trace events)
    rewrites_tables = False  # hooks rewrite the slots' coupling tables

    def __init__(
        self,
        segments: Sequence[int],
        model: ising.LayeredModel | None = None,
        priority: int = 0,
        user: str | None = None,
    ):
        segments = [int(s) for s in segments]
        if not segments or any(s <= 0 for s in segments):
            raise ValueError(f"segments must be positive sweep counts: {segments}")
        self._segments = segments
        self._seg = 0
        self._in_seg = 0
        self.sweeps_done = 0
        self.chunks = 0
        self.jid: int | None = None  # assigned by SampleServer.submit
        self.model = model
        self.priority = int(priority)
        self.user = "default" if user is None else str(user)
        self.parked: list | None = None  # ParkedSlot per slot while evicted
        self.preemptions = 0  # times evicted (stats; resume is bit-exact)
        # Scheduler bookkeeping (set by SampleServer.submit/_place): wall
        # and sweep-clock stamps for queue-wait reporting.
        self._submit_time = self._admit_time = None
        self._submit_sweep = self._admit_sweep = None
        self._seq = None  # admission-policy submission order

    def model_on(self, server) -> ising.LayeredModel:
        """The model this job samples when served by ``server``."""
        return self.model if self.model is not None else server.engine.model

    @property
    def done(self) -> bool:
        return self._seg >= len(self._segments)

    @property
    def segment_index(self) -> int:
        return self._seg

    def remaining_in_segment(self) -> int:
        if self.done:
            return 0
        return self._segments[self._seg] - self._in_seg

    def total_remaining(self) -> int:
        return sum(self._segments[self._seg :]) - self._in_seg

    def advance(self, k: int) -> bool:
        """Record ``k`` sweeps of progress; True iff a segment boundary was
        reached (the scheduler then runs `on_segment`)."""
        if k <= 0 or k > self.remaining_in_segment():
            raise ValueError(
                f"advance({k}) outside segment (remaining "
                f"{self.remaining_in_segment()})"
            )
        self._in_seg += k
        self.sweeps_done += k
        self.chunks += 1
        if self._in_seg == self._segments[self._seg]:
            self._seg += 1
            self._in_seg = 0
            return True
        return False

    # -- snapshot/restore (serve_mc.snapshot) ---------------------------------
    #
    # A job serializes to a JSON-safe ``meta`` dict plus a flat
    # ``{name: ndarray}`` dict.  ``meta`` carries everything the segment
    # bookkeeping and the admission policy need to continue exactly where
    # an uninterrupted run would be (progress counters, priority/user,
    # submission seq, sweep-clock stamps); arrays carry parked-slot state
    # and the subclass's own tensors.  The job's private model (if any) is
    # serialized by `serve_mc.snapshot` alongside, not here.

    def _snapshot_base(self) -> tuple[dict, dict]:
        meta = {
            "kind": self.kind,
            "jid": self.jid,
            "segments": list(self._segments),
            "seg": self._seg,
            "in_seg": self._in_seg,
            "sweeps_done": self.sweeps_done,
            "chunks": self.chunks,
            "priority": self.priority,
            "user": self.user,
            "preemptions": self.preemptions,
            "seq": self._seq,
            "submit_sweep": self._submit_sweep,
            "admit_sweep": self._admit_sweep,
            # Wall-clock wait ACCRUED so far for a still-queued job.
            # Restore re-anchors `_submit_time` to ``now - waited_s``, so
            # queue-wait reporting is downtime-invariant: the seconds a
            # process spent dead between save and restore never show up
            # as queue latency (tests/test_placement.py pins this).
            "waited_s": (
                time.perf_counter() - self._submit_time
                if self._submit_time is not None and self._admit_sweep is None
                else None
            ),
        }
        arrays: dict = {}
        if self.parked is not None:
            meta["num_parked"] = len(self.parked)
            meta["parked_tables"] = any(
                p.tables is not None for p in self.parked
            )
            for i, p in enumerate(self.parked):
                for name, v in zip(
                    sweep_engine.SweepCarry._fields, p.carry
                ):
                    arrays[f"parked/{i}/carry/{name}"] = np.asarray(v)
                if p.tables is not None:
                    for k, v in p.tables.items():
                        arrays[f"parked/{i}/tables/{k}"] = np.asarray(v)
        return meta, arrays

    def _restore_base(self, meta: dict, arrays: dict) -> None:
        self.jid = meta["jid"]
        self._seg = int(meta["seg"])
        self._in_seg = int(meta["in_seg"])
        self.sweeps_done = int(meta["sweeps_done"])
        self.chunks = int(meta["chunks"])
        self.preemptions = int(meta["preemptions"])
        self._seq = meta["seq"]
        self._submit_sweep = meta["submit_sweep"]
        self._admit_sweep = meta["admit_sweep"]
        # Wall-clock stamps cannot survive a process boundary raw, so a
        # queued job's submit time is re-anchored to ``now - waited_s``:
        # the wait it had ACCRUED at snapshot time carries over, while
        # process downtime between save and restore contributes nothing
        # (downtime-invariant queue-wait; sweep-clock waits, which the
        # policies use, are exact via the stamps above either way).
        now = time.perf_counter()
        waited = meta.get("waited_s")
        self._submit_time = now - float(waited) if waited is not None else now
        self._admit_time = (
            self._submit_time if self._admit_sweep is not None else None
        )
        if meta.get("num_parked"):
            parked = []
            for i in range(meta["num_parked"]):
                carry = sweep_engine.SweepCarry(
                    *(
                        jnp.asarray(arrays[f"parked/{i}/carry/{name}"])
                        for name in sweep_engine.SweepCarry._fields
                    )
                )
                tables = None
                prefix = f"parked/{i}/tables/"
                tabs = {
                    k[len(prefix) :]: jnp.asarray(v)
                    for k, v in arrays.items()
                    if k.startswith(prefix)
                }
                if tabs:
                    tables = tabs
                parked.append(sweep_engine.ParkedSlot(carry, tables))
            self.parked = parked

    def snapshot_state(self) -> tuple[dict, dict]:
        """(json-safe meta, {name: ndarray}) capturing this job exactly."""
        raise NotImplementedError

    @classmethod
    def from_snapshot(cls, meta: dict, arrays: dict, model=None):
        """Rebuild a job from `snapshot_state` output (inverse, bit-exact)."""
        raise NotImplementedError


class AnnealJob(_ScheduledJob):
    """One slot, one seed, a piecewise-constant beta schedule.

    ``schedule`` is a list of ``(num_sweeps, beta)`` pairs; ``beta=None``
    means the model's default.  Single-segment jobs are plain constant-
    temperature sampling; multi-segment jobs are annealing ladders.
    """

    kind = "anneal"

    def __init__(
        self,
        seed: int,
        schedule: Sequence[tuple[int, float | None]],
        spins: np.ndarray | None = None,
        model: ising.LayeredModel | None = None,
        priority: int = 0,
        user: str | None = None,
    ):
        super().__init__(
            [s for s, _ in schedule], model=model, priority=priority, user=user
        )
        self.seed = int(seed)
        self._betas = [b if b is None else float(b) for _, b in schedule]
        self._init_spins = None if spins is None else np.asarray(spins, np.float32)

    @classmethod
    def constant(
        cls,
        seed: int,
        sweeps: int,
        beta: float | None = None,
        model: ising.LayeredModel | None = None,
        priority: int = 0,
        user: str | None = None,
    ):
        return cls(seed, [(sweeps, beta)], model=model, priority=priority,
                   user=user)

    @classmethod
    def ramp(
        cls,
        seed: int,
        beta_start: float,
        beta_end: float,
        steps: int,
        sweeps_per_step: int,
        model: ising.LayeredModel | None = None,
        priority: int = 0,
        user: str | None = None,
    ):
        """Linear beta ramp: ``steps`` segments of ``sweeps_per_step``."""
        betas = np.linspace(beta_start, beta_end, steps)
        return cls(
            seed, [(sweeps_per_step, float(b)) for b in betas], model=model,
            priority=priority, user=user,
        )

    def snapshot_state(self) -> tuple[dict, dict]:
        meta, arrays = self._snapshot_base()
        meta["seed"] = self.seed
        meta["betas"] = list(self._betas)  # None entries survive as JSON null
        if self._init_spins is not None:
            arrays["init_spins"] = self._init_spins
        return meta, arrays

    @classmethod
    def from_snapshot(cls, meta: dict, arrays: dict, model=None):
        job = cls(
            meta["seed"],
            list(zip(meta["segments"], meta["betas"])),
            spins=arrays.get("init_spins"),
            model=model,
            priority=meta["priority"],
            user=meta["user"],
        )
        job._restore_base(meta, arrays)
        return job

    def _beta(self, server, seg: int) -> float:
        b = self._betas[seg]
        return float(self.model_on(server).beta) if b is None else b

    def current_beta(self, server) -> float:
        return self._beta(server, self._seg)

    # -- scheduler interface --------------------------------------------------

    def init_carries(self, server) -> list[sweep_engine.SweepCarry]:
        return [
            server.engine.init_slot_carry(
                seed=self.seed,
                spins=self._init_spins,
                beta=self._beta(server, 0),
                model=self.model,
            )
        ]

    def on_segment(self, server, carry, slots):
        with server.telemetry.span("anneal.hook"):
            if not self.done:
                server.queue_beta(slots[0], self.current_beta(server))
            return carry

    def finalize(self, server, slots) -> JobResult:
        eng, m = server.engine, self.model_on(server)
        with server.telemetry.span("engine.extract", cat="engine"):
            sub = eng.extract_slot(server.carry, slots[0])
            spins = eng.spins_flat(sub)[0]
            final_beta = float(np.asarray(sub.betas)[0])
        return JobResult(
            jid=self.jid,
            spins=spins,
            energy=observables.energies(m, spins),
            magnetization=observables.magnetization(spins),
            sweeps_done=self.sweeps_done,
            chunks=self.chunks,
            extras={
                "final_beta": final_beta,
                "preemptions": self.preemptions,
            },
        )


class SQAJob(_ScheduledJob):
    """Simulated quantum annealing: one slot, one seed, a transverse-field
    ramp over the Trotter slices of one problem (path-integral Monte
    Carlo; Santoro et al., Science 295, 2427 (2002)).

    ``model`` is the problem: its fields, couplings and slice count L
    (its tau couplings and beta play no part); None means the server's
    base model.  Beta stays ``beta``; segment k (``sweeps_per_gamma``
    sweeps) sweeps `qmc.QMCProblem.layered_model(beta, gammas[k])`: h and
    J over L, and tau couplings from ``gammas[k]``.  At each boundary the
    hook hands the next Gamma's tau row to the server (`queue_tau`),
    which writes every due row of the step in one `set_slot_tau` call.
    The slot's tables change as the job runs, so it needs a
    multi-tenant server.  The result is the final slices and their
    layered energy under the last Gamma; ``extras`` hold that Gamma,
    the layer-majority assignment and its problem energy.
    """

    kind = "sqa"
    rewrites_tables = True

    def __init__(
        self,
        seed: int,
        beta: float,
        gammas: Sequence[float],
        sweeps_per_gamma: int,
        model: ising.LayeredModel | None = None,
        priority: int = 0,
        user: str | None = None,
    ):
        gammas = [float(g) for g in gammas]
        super().__init__(
            [int(sweeps_per_gamma)] * len(gammas), model=model,
            priority=priority, user=user,
        )
        self.seed = int(seed)
        self.beta = float(beta)
        self.gammas = gammas

    def snapshot_state(self) -> tuple[dict, dict]:
        meta, arrays = self._snapshot_base()
        meta["seed"] = self.seed
        meta["beta"] = self.beta
        meta["sweeps_per_gamma"] = self._segments[0]
        arrays["gammas"] = np.asarray(self.gammas, np.float64)
        return meta, arrays

    @classmethod
    def from_snapshot(cls, meta: dict, arrays: dict, model=None):
        job = cls(
            meta["seed"],
            meta["beta"],
            arrays["gammas"].tolist(),
            meta["sweeps_per_gamma"],
            model=model,
            priority=meta["priority"],
            user=meta["user"],
        )
        job._restore_base(meta, arrays)
        return job

    def problem(self, server) -> qmc.QMCProblem:
        return qmc.QMCProblem.of(
            self.model if self.model is not None else server.engine.model
        )

    def current_gamma(self) -> float:
        return self.gammas[min(self._seg, len(self.gammas) - 1)]

    def tau_row(self, server) -> np.ndarray:
        """The slot's tau couplings for the current Gamma: the
        ``tau_J`` of `model_on`, without building the model."""
        pb = self.problem(server)
        jt = qmc.tau_coupling(self.beta, self.current_gamma(), pb.L)
        return np.full(pb.h.shape, jt, dtype=np.float32)

    def model_on(self, server) -> ising.LayeredModel:
        """The layered model of the current Gamma (the last one once the
        ramp is done): what the slot sweeps now, and what admission
        splices or a resumed slot's tables hold."""
        return self.problem(server).layered_model(self.beta, self.current_gamma())

    # -- scheduler interface --------------------------------------------------

    def init_carries(self, server) -> list[sweep_engine.SweepCarry]:
        return [
            server.engine.init_slot_carry(
                seed=self.seed, beta=self.beta, model=self.model_on(server)
            )
        ]

    def on_segment(self, server, carry, slots):
        with server.telemetry.span("sqa.hook"):
            if not self.done:
                server.queue_tau(slots[0], self.tau_row(server))
            return carry

    def finalize(self, server, slots) -> JobResult:
        eng, m = server.engine, self.model_on(server)
        with server.telemetry.span("engine.extract", cat="engine"):
            spins = eng.spins_flat(eng.extract_slot(server.carry, slots[0]))[0]
        pb = self.problem(server)
        assign = qmc.layer_majority(spins, m.n, m.L)
        return JobResult(
            jid=self.jid,
            spins=spins,
            energy=observables.energies(m, spins),
            magnetization=observables.magnetization(spins),
            sweeps_done=self.sweeps_done,
            chunks=self.chunks,
            extras={
                "final_gamma": self.current_gamma(),
                "assignment": assign,
                "problem_energy": pb.energy(assign),
                "preemptions": self.preemptions,
            },
        )


class PTJob(_ScheduledJob):
    """A whole parallel-tempering workload as ONE multi-slot job.

    Occupies R slots (one per replica).  Every segment is one PT round of
    ``sweeps_per_round`` sweeps; at each boundary the job makes one
    `tempering.swap_slots` call on the shared carry, which reads its
    slots' spins and betas, decides the swaps with the standalone driver's
    energies and decision body, and writes the swapped betas back in the
    same call.  Seeding reproduces
    `tempering.init_pt` exactly (replica b gets RNG lane seeds
    ``lane_seeds(R, V, seed)[b*V:(b+1)*V]`` and spins
    ``init_spins(m, seed*1000 + b)``), so the result is bit-identical to
    `tempering.run_parallel_tempering` regardless of slot placement.
    """

    kind = "pt"

    def __init__(
        self,
        seed: int,
        betas: np.ndarray,
        num_rounds: int,
        sweeps_per_round: int = 1,
        model: ising.LayeredModel | None = None,
        priority: int = 0,
        user: str | None = None,
    ):
        if num_rounds < 1:
            raise ValueError(f"num_rounds must be >= 1, got {num_rounds}")
        super().__init__(
            [int(sweeps_per_round)] * int(num_rounds), model=model,
            priority=priority, user=user,
        )
        self.seed = int(seed)
        self.betas = np.asarray(betas, np.float32)
        self.num_slots = len(self.betas)
        self.swap_rng = mt19937.mt_init(self.seed + 17)  # as tempering.init_pt
        self.swap_accept = jnp.int32(0)
        self.swap_propose = jnp.int32(0)
        self._energy_tables = None  # built on first swap for a private model
        self._rows = None  # (slots, device array of their carry rows)

    def snapshot_state(self) -> tuple[dict, dict]:
        meta, arrays = self._snapshot_base()
        meta["seed"] = self.seed
        meta["sweeps_per_round"] = self._segments[0]
        # The swap decision stream: generator columns at their exact
        # position plus the accept/propose tallies.  `_energy_tables` and
        # `_rows` are pure caches — rebuilt on first post-restore swap,
        # bit-identically (float32 arrays round-trip exactly).
        meta["swap_accept"] = int(self.swap_accept)
        meta["swap_propose"] = int(self.swap_propose)
        arrays["betas"] = self.betas
        arrays["swap_rng"] = np.asarray(self.swap_rng)
        return meta, arrays

    @classmethod
    def from_snapshot(cls, meta: dict, arrays: dict, model=None):
        job = cls(
            meta["seed"],
            arrays["betas"],
            num_rounds=len(meta["segments"]),
            sweeps_per_round=meta["sweeps_per_round"],
            model=model,
            priority=meta["priority"],
            user=meta["user"],
        )
        job._restore_base(meta, arrays)
        job.swap_rng = jnp.asarray(arrays["swap_rng"])
        job.swap_accept = jnp.int32(meta["swap_accept"])
        job.swap_propose = jnp.int32(meta["swap_propose"])
        return job

    # -- scheduler interface --------------------------------------------------

    def init_carries(self, server) -> list[sweep_engine.SweepCarry]:
        eng, m = server.engine, self.model_on(server)
        lanes = eng._slot_lanes()
        seeds = sweep_engine.lane_seeds(self.num_slots, lanes, self.seed)
        return [
            eng.init_slot_carry(
                seed=self.seed,
                spins=ising.init_spins(m, seed=self.seed * 1000 + b),
                beta=float(self.betas[b]),
                rng_seeds=seeds[b * lanes : (b + 1) * lanes],
                model=self.model,
            )
            for b in range(self.num_slots)
        ]

    def _slot_rows(self, eng, slots):
        """The ladder's physical carry rows (identity unless the engine
        pads an uneven capacity vector) as an int32 device array, built
        once per placement: a park and resume or a rebalance moves the
        job to other slots, and only then is it rebuilt."""
        key = tuple(int(b) for b in slots)
        if self._rows is None or self._rows[0] != key:
            rows = eng.phys_slots(key).astype(np.int32)
            if eng.mesh is None:
                self._rows = (key, jnp.asarray(rows))
            else:  # replicated beside the sharded carry
                self._rows = (key, jax.device_put(
                    rows, NamedSharding(eng.mesh, P())))
        return self._rows[1]

    def _swap_energy_tables(self, eng):
        """Energy tables of the job's model: the engine's when the job has
        none (bit-path identical to the single-model server), else built
        once per job from the private model."""
        if self.model is None:
            return tempering.energy_tables(eng)
        if self._energy_tables is None:
            self._energy_tables = tempering.model_energy_tables(self.model)
        return self._energy_tables

    def on_segment(self, server, carry, slots):
        with server.telemetry.span("pt.hook"):
            eng, tel = server.engine, server.telemetry
            parity = (self._seg - 1) % 2  # round index just completed, as
            # the ``r % 2`` of `tempering.run_parallel_tempering`
            # Placement-aware routing: the cross-device energy gather is only
            # needed when the ladder actually SPANS devices.  A device-local
            # placement (what affine admission produces) takes the same
            # one-call `swap_slots` path as an unsharded server.  Both paths
            # share `_swap_decide` and `lane_energy`, so routing by placement
            # is bit-invisible (tests/test_placement.py).
            spans = (
                eng.mesh is not None
                and len({eng.slot_device(b) for b in slots}) > 1
            )
            (server._c_swap_cross if spans else server._c_swap_local).add(1)
            if spans:
                # Cross-device path: a ladder spanning devices must NOT gather
                # its slots' spins (that is the whole carry).  Each device
                # evaluates its own slots' energies (`slot_energies`, zero
                # spin movement); only the job's R energy/beta scalars cross
                # devices, and the swap decision is the same `_swap_decide`
                # body as `swap_phase` — bit-identical to the resident path.
                # `slot_energies` is already a LOGICAL (B,) view; the carry's
                # betas row is PHYSICAL and needs the translated indices.
                lidx = np.asarray(slots, np.int64)
                with tel.span("pt.gather"):
                    energies = eng.slot_energies(carry)[lidx]
                with tel.span("pt.swap"):
                    betas, self.swap_rng, self.swap_accept, self.swap_propose = (
                        tempering.swap_phase_from_energies(
                            carry.betas[eng.phys_slots(slots)],
                            energies,
                            self.swap_rng,
                            self.swap_accept,
                            self.swap_propose,
                            jnp.asarray(parity, jnp.int32),
                            eng.exp_flavor,
                        )
                    )
                return eng.set_slot_betas(carry, slots, betas)
            # Resident path: one device call reads the ladder's spins and
            # betas out of the carry and writes the swapped betas back.
            with tel.span("pt.swap"):
                betas, self.swap_rng, self.swap_accept, self.swap_propose = (
                    tempering.swap_slots(
                        carry.spins,
                        carry.betas,
                        self._slot_rows(eng, slots),
                        self.swap_rng,
                        self.swap_accept,
                        self.swap_propose,
                        np.int32(parity),
                        *self._swap_energy_tables(eng),
                        eng.model.n,
                        eng.exp_flavor,
                    )
                )
                if eng.mesh is not None:  # keep the betas row sharded
                    betas = jax.device_put(
                        betas, NamedSharding(eng.mesh, P("data"))
                    )
            return carry._replace(betas=betas)

    def finalize(self, server, slots) -> JobResult:
        eng, m = server.engine, self.model_on(server)
        with server.telemetry.span("engine.extract", cat="engine"):
            spins = np.stack(
                [eng.spins_flat(eng.extract_slot(server.carry, b))[0] for b in slots]
            )
            betas = np.asarray(server.carry.betas)[eng.phys_slots(slots)]
        return JobResult(
            jid=self.jid,
            spins=spins,
            energy=observables.energies(m, spins),
            magnetization=observables.magnetization(spins),
            sweeps_done=self.sweeps_done,
            chunks=self.chunks,
            extras={
                "betas": betas,
                "swap_accept": int(self.swap_accept),
                "swap_propose": int(self.swap_propose),
                "preemptions": self.preemptions,
            },
        )
