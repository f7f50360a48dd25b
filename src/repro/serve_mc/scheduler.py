"""SampleServer — continuous-batching annealing service over one SweepEngine.

The serving analogue of `launch/serve.py`'s token loop, with replica
slots in place of sequence slots: ONE resident `SweepEngine` of ``slots``
replicas stays alive for the server's lifetime, and every scheduling
round advances the whole batch by a fixed-size chunk of sweeps as a
single launch (for ``backend="pallas"`` one fused kernel launch — the
many-replica throughput play of Weigel & Yavors'kii, arXiv:1107.5463,
applied to user jobs).  Between chunks the scheduler does the bookkeeping
the GPU/TPU never sees:

  admit    ask the server's `AdmissionPolicy` which queued jobs enter the
           free slots (plus which active jobs to checkpoint-preempt for
           them); splice each admitted job's per-slot carry (spins,
           fields, beta, RNG lane columns) into its slots
           (`SweepEngine.splice_slot` / `resume_slot`).
  chunk    ``min(chunk_sweeps, min remaining-in-segment over active
           jobs)`` — chunks never cross a segment boundary, so per-job
           beta schedules and tempering swap points land exactly where a
           solo run would put them.  ``chunk_sweeps="adaptive"`` replaces
           the static knob with `AdaptiveChunker`: a measured per-launch
           cost EWMA and the queue depth pick each chunk from a bounded
           power-of-two menu (latency SLO vs throughput, with a bounded
           jit cache).
  hooks    jobs whose segment ended run `on_segment` (anneal jobs hand
           over their slot's next beta and SQA jobs its tau row, each
           kind written for every due slot in one engine call after the
           loop; PT jobs run the swap phase over their slots).
  retire   finished jobs are finalized (`core/observables.py` summary of
           the extracted slot), their slots returned to the free list.

Determinism contract: a job's final spins/energy/RNG are bit-identical
whether it ran solo (``slots=1``) or packed with arbitrary neighbours
across admit/retire slot reuse, because (a) each slot owns private RNG
lane columns that advance by a fixed number of blocks per sweep
regardless of batch size, (b) chunk boundaries never change the stream
position (it is a pure function of sweeps completed), and (c) chunks stop
at segment boundaries.  Idle slots keep sweeping whatever they last held
— wasted work, not wrong work; utilization is reported in `stats()`.

Admission is PLUGGABLE (DESIGN.md §Scheduling).  ``policy="fifo"`` is
the historical queue: strict submission order, head-of-line blocking
when the head is a wide multi-slot job.  ``policy="backfill"`` adds
priority classes, EASY backfill (a narrow job may jump a blocked wide
job iff it provably cannot delay the wide job's reserved start — exact,
not estimated: sweep budgets are known) and checkpoint-preemption (a
blocked higher-priority job may evict lower-priority active jobs at a
chunk boundary; their slots are parked via `SweepEngine.park_slot` and
resumed bit-exactly later).  ``policy="fair"`` additionally orders each
priority tier by per-user weighted fairness (deficit-style served-cost
accounting over user queues), so one heavy user cannot starve others.
Scheduling decides WHEN a job runs, never what it computes: per-job
results are bit-identical under every policy.

``multi_tenant=True`` builds the engine with `SweepEngine.build_multi`:
each slot additionally owns a row of batched per-slot coupling tables, so
jobs over DIFFERENT models of one lattice (same topology, different
couplings/fields — e.g. disorder realizations) pack into the same fused
launches; admission splices the job model's tables next to its carry.
The determinism contract extends unchanged: slot tables are as private
as the carry rows, so solo == packed still holds bit for bit, and a
model-less job on a multi-tenant server is bit-identical to the same job
on a single-model server (DESIGN.md §Multi-tenancy).

``mesh=...`` (a 1-D ``("data",)`` mesh, `launch.mesh.make_slot_mesh`)
shards the slot pool over D devices: ``slots`` stays the GLOBAL count,
every chunk advances all slots as one `shard_map` launch with zero
cross-device traffic.  Admission is PLACEMENT-AWARE (`SlotPool`): free
lists are keyed by device over the mesh's contiguous per-device blocks,
policies plan placements (not just jobs), multi-slot jobs — PT ladders
above all — pack onto ONE device whenever any device has room (spanning
only under fragmentation, and a chunk-boundary rebalancer migrates
parked slots to undo even that), and a device-local ladder's swap phase
takes the in-device fast path instead of the cross-device energy gather.
``capacities=[...]`` makes the mesh HETEROGENEOUS: each device owns that
many global slots (prefix-sum blocks instead of the equal ``B/D``
split), the engine pads its physical layout per device, and every
placement tie-break ranks devices by RELATIVE free capacity so a big
host and a small accelerator are compared fairly.  Bit-exactness
extends across the mesh AND across placements: D devices == 1 device ==
any slot assignment — even an uneven one — for every job (DESIGN.md
§Mesh, tests/test_sharded.py, tests/test_placement.py,
tests/test_hetero.py).

TELEMETRY (DESIGN.md §Observability): the server owns a
`repro.obs.Telemetry` registry — counters/gauges/histograms that
`stats()` READS (one source of numbers; a metrics scrape and stats() can
never disagree) plus a bounded ring of Chrome-trace events: sync spans
for scheduler phases, one complete event per engine launch (chunk size,
jobs aboard, wall clock, compile-vs-steady, device count), async spans
per job lifecycle (submit -> admit -> segments -> retire, park/resume
with reasons), and plan events for admission decisions.  ``telemetry=
False`` turns event recording off (counters keep counting — stats needs
them); either way results are bit-identical, and the overhead of "on" is
measured and gated (benchmarks/serve_bench.py telemetry_overhead), not
assumed.  ``stream=`` attaches an `obs.ObservableStream`: an opt-in
per-chunk energy/magnetization/best-so-far tap over the active jobs —
the hook the ROADMAP async front-end will stream to clients.  On a
sharded engine the launch probe also times each DEVICE's shard
(`SweepEngine.device_ready_times`) and feeds an `obs.LaunchSkewMonitor`,
so one straggling device is detected, not averaged away.
"""

from __future__ import annotations

import bisect
import dataclasses
import time
from collections import Counter, defaultdict, deque
from typing import List

import jax
import numpy as np

from repro.core import ising
from repro.core.engine import SweepEngine, normalize_capacities
from repro.obs import LaunchSkewMonitor, ObservableStream, Telemetry

from repro.serve_mc.jobs import JobResult


# -----------------------------------------------------------------------------
# Admission policies (DESIGN.md §Scheduling).
#
# A policy owns the queue of not-yet-running jobs and, between launches,
# PLANS one scheduling round: which queued jobs enter the free slots and
# which active jobs get checkpoint-preempted to make room.  The plan is
# pure bookkeeping over slot counts and exact remaining sweep budgets
# (every job's duration is known, not estimated — sampling budgets are
# deterministic); the server executes it with the engine's slot APIs.
# Policies never touch carries, so they cannot affect results: a job's
# spins/energy/RNG are bit-identical under every policy.
# -----------------------------------------------------------------------------


def _job_cost(job) -> int:
    """Service demand in slot-sweeps (the unit fairness accounts in)."""
    return job.num_slots * job.total_remaining()


class SlotPool:
    """Free lists keyed by DEVICE over the global slot index space.

    The mesh lays the batch axis out as contiguous ``[D, B/D]`` blocks
    (DESIGN.md §Mesh), so global slot ``b`` lives on device ``b // (B/D)``
    — a pure function of the index, which is what lets the scheduler name
    locality instead of letting GSPMD guess it.  The pool keeps one SORTED
    free list per device (``bisect.insort`` on release; the old flat list
    re-sorted on every admission) and guards every transition: releasing a
    slot that is already free, or taking one that is not, raises instead
    of silently double-booking a launch.

    ``mode`` picks the allocation discipline:

    * ``"affine"`` (the default) packs a multi-slot job onto ONE device
      whenever any device has room — best-fit over the per-device free
      counts, so narrow jobs fill the emptiest-fitting device last and a
      wide ladder keeps finding whole devices — and falls back to a
      SPANNING placement (fewest devices, most-free first) only when
      fragmentation forces it.  Placement never changes results (slot
      state is slot-private); it changes which PT swap phases stay on the
      in-device fast path.
    * ``"flat"`` reproduces the historical single-list behavior exactly
      (lowest global indices first, devices ignored) — the baseline the
      placement bench compares against.

    With ``devices == 1`` the two modes coincide, so a single-device
    server is bit-and-schedule-identical to the pre-placement code.
    """

    def __init__(
        self,
        slots: int,
        devices: int = 1,
        mode: str = "affine",
        capacities=None,
    ):
        if devices < 1:
            raise ValueError(f"devices must be >= 1, got {devices}")
        if mode not in ("affine", "flat"):
            raise ValueError(
                f"placement mode must be 'affine' or 'flat', got {mode!r}"
            )
        self.slots = int(slots)
        self.devices = int(devices)
        # One validation path with the engine: equal split (and its
        # historical "divide evenly" error) when capacities is None,
        # else the explicit per-device vector.
        self.capacities = normalize_capacities(
            self.devices, self.slots, capacities
        )
        # Largest per-device block: the bound on how wide a job can be
        # placed without spanning (planner gates check W <= cap).
        self.cap = max(self.capacities)
        self.mode = mode
        self._cum = [0]
        for c in self.capacities:
            self._cum.append(self._cum[-1] + c)
        self._free: list[list[int]] = [
            list(range(self._cum[d], self._cum[d + 1]))
            for d in range(self.devices)
        ]

    def device_of(self, b: int) -> int:
        """Device owning global slot ``b``: the prefix-sum bracket of the
        capacity vector (with equal capacities this is exactly the
        historical ``b // (B/D)`` contiguous-block rule)."""
        return bisect.bisect_right(self._cum, int(b)) - 1

    def _rel_free(self, d: int) -> float:
        """Free fraction of device ``d`` (0.0 for a zero-capacity device).

        Tie-break currency on heterogeneous pools: comparing absolute
        free counts would treat "2 of 8 free" as fuller than "1 of 1
        free"; relative capacity ranks devices by how full they really
        are.  On equal-capacity pools every comparison below reduces to
        the historical absolute-count order (same denominator), so PR 9
        placements are reproduced decision-for-decision.
        """
        c = self.capacities[d]
        return len(self._free[d]) / c if c else 0.0

    @property
    def total_free(self) -> int:
        return sum(len(f) for f in self._free)

    def free_by_device(self) -> list[int]:
        return [len(f) for f in self._free]

    def free_on(self, d: int) -> list[int]:
        return list(self._free[d])

    def flat_free(self) -> list[int]:
        """All free slots as one sorted global list (snapshot format)."""
        return [b for f in self._free for b in f]

    def clone(self) -> "SlotPool":
        out = SlotPool.__new__(SlotPool)
        out.slots, out.devices = self.slots, self.devices
        out.cap, out.mode = self.cap, self.mode
        out.capacities, out._cum = self.capacities, list(self._cum)
        out._free = [list(f) for f in self._free]
        return out

    def release(self, b: int) -> None:
        """Return one slot to its device's free list (sorted insert);
        raises on double-free — a slot on a free list twice silently
        double-books a later launch, the bug class this pool closes."""
        b = int(b)
        if not 0 <= b < self.slots:
            raise ValueError(f"slot {b} outside pool of {self.slots}")
        f = self._free[self.device_of(b)]
        i = bisect.bisect_left(f, b)
        if i < len(f) and f[i] == b:
            raise RuntimeError(f"slot {b} released twice (double-free)")
        f.insert(i, b)

    def release_all(self, slots) -> None:
        for b in slots:
            self.release(b)

    def take(self, slots) -> None:
        """Claim specific slots; raises if any is not currently free."""
        for b in slots:
            b = int(b)
            f = self._free[self.device_of(b)]
            i = bisect.bisect_left(f, b)
            if i >= len(f) or f[i] != b:
                raise RuntimeError(
                    f"slot {b} is not free (placement double-books slots)"
                )
            del f[i]

    def _take_lowest(self, d: int, n: int) -> list[int]:
        taken, self._free[d] = self._free[d][:n], self._free[d][n:]
        return taken

    def alloc(self, n: int, avoid: int | None = None) -> tuple[int, ...]:
        """Allocate ``n`` slots under the pool's placement mode.

        ``avoid`` (affine mode) steers the placement off one device —
        other devices are preferred at every stage — but is a preference,
        not a guarantee: callers enforcing a hard budget on the avoided
        device count the returned slots themselves.
        """
        if n < 1:
            raise ValueError(f"alloc needs n >= 1, got {n}")
        if n > self.total_free:
            raise RuntimeError(
                f"alloc({n}) with only {self.total_free} slots free"
            )
        if self.mode == "flat":
            # Historical behavior: lowest global indices, devices ignored.
            taken: list[int] = []
            for d in range(self.devices):
                take = min(n - len(taken), len(self._free[d]))
                taken.extend(self._take_lowest(d, take))
                if len(taken) == n:
                    break
            return tuple(taken)
        # Device-affine: best-fit device (smallest RELATIVE free fraction
        # that still fits, then fewest absolute free, ties to the lowest
        # index) keeps the emptiest devices whole for wide ladders across
        # uneven capacity vectors; `avoid` is considered only when
        # nothing else fits.
        fits = [d for d in range(self.devices) if len(self._free[d]) >= n]
        pick = [d for d in fits if d != avoid] or fits
        if pick:
            d = min(pick, key=lambda d: (self._rel_free(d), len(self._free[d]), d))
            return tuple(self._take_lowest(d, n))
        # Spanning fallback: fragmentation forces a cross-device placement;
        # take from the relatively-emptiest devices first so the job
        # straddles as few devices as possible (the avoided device
        # contributes last).
        order = sorted(
            (d for d in range(self.devices) if self._free[d]),
            key=lambda d: (d == avoid, -self._rel_free(d), -len(self._free[d]), d),
        )
        taken = []
        for d in order:
            take = min(n - len(taken), len(self._free[d]))
            taken.extend(self._take_lowest(d, take))
            if len(taken) == n:
                break
        return tuple(taken)

    def restore_free(self, flat) -> None:
        """Reset the free lists from a flat global list (snapshot restore:
        the per-device keying is recomputed for THIS pool's device count,
        which is how a D=4 snapshot restores onto D=1 and vice versa)."""
        for f in self._free:
            f.clear()
        self.release_all(int(b) for b in flat)


class PlacementPlanner(int):
    """The free-pool view handed to `AdmissionPolicy.plan`.

    Subclasses ``int`` so the historical ``plan(free, active)`` contract
    survives unchanged: custom policies that treat ``free`` as the
    free-slot count (compare, subtract) keep working and may keep
    returning bare jobs — the server then places them itself.  Built-in
    policies use the placement API instead: `alloc`/`putback` simulate
    placements against a PRIVATE clone of the server's pool (the real
    pool mutates only when the server executes the plan), `release_job`
    models a planned preemption, and `slots_of` exposes where each active
    job sits so reservations can count freed slots per device.
    """

    def __new__(cls, pool: SlotPool, held: dict | None = None):
        return int.__new__(cls, pool.total_free)

    def __init__(self, pool: SlotPool, held: dict | None = None):
        self._pool = pool.clone()
        self._held = dict(held or {})  # id(job) -> slots tuple

    @classmethod
    def from_counts(cls, free: int, active=()) -> "PlacementPlanner":
        """A single-device planner synthesized from bare counts — the
        adapter behind direct ``plan(free_count, active)`` calls."""
        active = list(active)
        total = int(free) + sum(j.num_slots for j in active)
        pool = SlotPool(max(total, 1), devices=1)
        if total == 0:
            pool.take((0,))  # the padding slot is not actually free
        held, nxt = {}, int(free)
        for j in active:
            slots = tuple(range(nxt, nxt + j.num_slots))
            pool.take(slots)
            held[id(j)] = slots
            nxt += j.num_slots
        return cls(pool, held)

    @property
    def devices(self) -> int:
        return self._pool.devices

    @property
    def mode(self) -> str:
        return self._pool.mode

    @property
    def cap(self) -> int:
        return self._pool.cap

    @property
    def capacities(self) -> tuple:
        return self._pool.capacities

    @property
    def total_free(self) -> int:
        return self._pool.total_free

    def free_by_device(self) -> list[int]:
        return self._pool.free_by_device()

    def device_of(self, b: int) -> int:
        return self._pool.device_of(b)

    def slots_of(self, job) -> tuple:
        return self._held.get(id(job), ())

    def alloc(self, job, avoid: int | None = None) -> tuple[int, ...]:
        slots = self._pool.alloc(job.num_slots, avoid=avoid)
        self._held[id(job)] = slots
        return slots

    def putback(self, job) -> None:
        """Undo a simulated `alloc` (the candidate was rejected)."""
        self._pool.release_all(self._held.pop(id(job), ()))

    def release_job(self, job) -> tuple:
        """Model a planned preemption: the victim's slots free up."""
        slots = self._held.pop(id(job), ())
        self._pool.release_all(slots)
        return slots


class AdmissionPolicy:
    """FIFO admission: fill free slots in strict submission order.

    The base class doubles as the policy interface: `enqueue` receives
    submitted (and re-queued preempted) jobs, `plan` returns one round's
    ``(preempt_jobs, admit_jobs)`` given the free pool and the currently
    active jobs.  ``free`` arrives as a `PlacementPlanner` (an ``int``
    subclass whose value is the free-slot count): built-in policies call
    its placement API and return admits as ``(job, slots)`` pairs, while
    custom policies may keep treating it as a bare count and returning
    bare jobs — the server places those itself.  FIFO never preempts and
    never reorders, so a wide job at the queue head blocks everything
    behind it while slots idle — exactly the utilization leak the
    priority policies close.
    """

    name = "fifo"

    #: The server's sweep clock, refreshed before every `plan` call —
    #: policies that age waiting jobs (`PriorityBackfillPolicy`) read it;
    #: FIFO ignores it.
    clock = 0

    def __init__(self):
        self._queued: list = []
        self._seq = 0

    def enqueue(self, job) -> None:
        if getattr(job, "_seq", None) is None:
            job._seq = self._seq  # preempted jobs keep their original seq
            self._seq += 1
        self._queued.append(job)
        self._queued.sort(key=lambda j: j._seq)

    def __len__(self) -> int:
        return len(self._queued)

    def jobs(self) -> list:
        return list(self._queued)

    def plan(self, free, active: list) -> tuple[list, list]:
        planner = free if isinstance(free, PlacementPlanner) else None
        n_free = int(free)
        admit = []
        while self._queued and self._queued[0].num_slots <= n_free:
            job = self._queued.pop(0)
            n_free -= job.num_slots
            if planner is not None:
                admit.append((job, planner.alloc(job)))
            else:
                admit.append(job)  # legacy bare-count call: server places
        return [], admit


class PriorityBackfillPolicy(AdmissionPolicy):
    """Priority classes + EASY backfill + checkpoint-preemption, with
    optional per-user weighted fairness (``policy="fair"``).

    Candidate order: priority tiers are strict (higher first); within a
    tier, submission order — or, when ``fair=True``, weighted fair order:
    each user accumulates ``served += cost/weight`` (cost in slot-sweeps)
    as their jobs are admitted, and the tier is ordered by repeatedly
    taking the head job of the least-served user (deficit round-robin
    over user queues: a heavy user's backlog cannot starve a light one,
    because every admission pushes the heavy user's served level past the
    light user's).  A user entering the backlog is floored to the least
    served level of the users already waiting, so idle time cannot be
    banked into a later monopoly.

    One scheduling round walks the candidates:

    * fits -> admit.
    * first candidate that does NOT fit: try preemption — evict active
      jobs of strictly lower priority (lowest first) at this chunk
      boundary until the candidate fits; eviction parks each slot's
      carry (and coupling tables) for a later bit-exact resume, so
      preemption costs placement, never work.  If preemption cannot free
      enough, the candidate becomes the round's RESERVED job.
    * after a reservation exists, later candidates only BACKFILL: admit
      a candidate iff it fits the free list now and either (a) it
      retires within ``start`` sweeps — the reserved job's provably
      earliest start, when enough active jobs have retired — or (b) it
      needs no more than the ``spare`` slots left over at that start.
      Both are exact slot-count accounting over known budgets, so
      backfill can NEVER delay the reserved job (tests/test_scheduling).

    Reservation arithmetic (sweeps are the clock; all active slots
    advance in lockstep): with ``free`` slots free now and active jobs
    retiring after ``r_i`` more sweeps freeing ``k_i`` slots each, the
    reserved job (width W) starts at ``start = min r`` with
    ``free + sum(k_i : r_i <= r) >= W``, and
    ``spare = free + freed(start) - W``.

    PRIORITY AGING (``aging_sweeps > 0``): a queued job's EFFECTIVE
    priority for candidate ordering is ``priority + waited // aging_sweeps``
    with ``waited`` in sweeps since submission — so under sustained
    higher-tier traffic a priority-p job reaches tier p+k after at most
    ``k * aging_sweeps`` sweeps of waiting, which bounds cross-tier
    starvation (tests/test_scheduling.py).  Aging escalates ORDERING and
    reservation rights only; preemption keeps STATIC priorities (an aged
    priority-0 job may be admitted ahead of priority-1 arrivals, but never
    earns the right to evict genuinely higher-priority work).
    """

    def __init__(
        self,
        *,
        backfill: bool = True,
        preempt: bool = True,
        fair: bool = False,
        user_weights: dict[str, float] | None = None,
        aging_sweeps: int = 0,
    ):
        super().__init__()
        self.backfill = bool(backfill)
        self.preempt = bool(preempt)
        self.fair = bool(fair)
        self.user_weights = dict(user_weights or {})
        if aging_sweeps < 0:
            raise ValueError(f"aging_sweeps must be >= 0, got {aging_sweeps}")
        self.aging_sweeps = int(aging_sweeps)
        self.name = "fair" if self.fair else "backfill"
        self._served: dict[str, float] = {}  # user -> served cost / weight

    def _weight(self, user: str) -> float:
        w = float(self.user_weights.get(user, 1.0))
        if w <= 0:
            raise ValueError(f"user weight must be > 0, got {w} for {user!r}")
        return w

    def enqueue(self, job) -> None:
        if self.fair:
            backlogged = {j.user for j in self._queued}
            if job.user not in backlogged:
                # Entering the backlog: floor to the least-served waiting
                # user so service credit cannot be banked while idle.
                floor = min(
                    (self._served.get(u, 0.0) for u in backlogged),
                    default=0.0,
                )
                self._served[job.user] = max(
                    self._served.get(job.user, 0.0), floor
                )
            if len(self._served) > self.SERVED_LEDGER_MAX:
                # Compact: users with nothing queued re-enter floored
                # later, so dropping them only forfeits their surplus.
                keep = backlogged | {job.user}
                self._served = {
                    u: v for u, v in self._served.items() if u in keep
                }
        super().enqueue(job)

    def _eff_priority(self, job) -> int:
        """Ordering priority: static class plus one tier per
        ``aging_sweeps`` sweeps waited since submission."""
        if not self.aging_sweeps:
            return job.priority
        waited = max(0, self.clock - (job._submit_sweep or 0))
        return job.priority + waited // self.aging_sweeps

    def _order(self) -> list:
        """Queued jobs in admission-candidate order."""
        if not self.fair:
            return sorted(
                self._queued, key=lambda j: (-self._eff_priority(j), j._seq)
            )
        out = []
        tiers: dict[int, list] = defaultdict(list)
        for j in self._queued:
            tiers[self._eff_priority(j)].append(j)
        for prio in sorted(tiers, reverse=True):
            queues: dict[str, deque] = defaultdict(deque)
            for j in sorted(tiers[prio], key=lambda j: j._seq):
                queues[j.user].append(j)
            proj = {u: self._served.get(u, 0.0) for u in queues}
            while queues:
                u = min(queues, key=lambda v: (proj[v], v))
                j = queues[u].popleft()
                out.append(j)
                proj[u] += _job_cost(j) / self._weight(u)
                if not queues[u]:
                    del queues[u]
        return out

    #: Bound on the served-cost ledger; past it, users with no queued
    #: jobs are dropped (they re-enter floored, losing nothing but their
    #: surplus) so a resident server's memory stays bounded however many
    #: distinct user ids traffic brings.
    SERVED_LEDGER_MAX = 10_000

    def _charge(self, job) -> None:
        """Record an admission for fairness accounting.  Re-admissions of
        a preempted job are NOT re-charged: its full cost was charged
        when it first entered, and eviction already costs the user
        placement time — double-charging would penalize preemption
        victims twice."""
        if self.fair and job.parked is None:
            u = job.user
            self._served[u] = (
                self._served.get(u, 0.0) + _job_cost(job) / self._weight(u)
            )

    @staticmethod
    def _reservation(job, free: int, running: list) -> tuple[int, int]:
        """(start, spare) for a blocked ``job``: the exact sweep count at
        which enough slots will have retired, and the slots left over."""
        need = job.num_slots - free
        events = sorted((j.total_remaining(), j.num_slots) for j in running)
        acc, start = 0, None
        for r, k in events:
            acc += k
            if acc >= need:
                start = r
                break
        assert start is not None, "submit() bounds num_slots by server slots"
        freed = sum(k for r, k in events if r <= start)
        return start, free + freed - job.num_slots

    def _reservation_placed(self, job, planner, running) -> tuple:
        """(start, spare, d_star, spare_dev) for a blocked ``job``.

        ``start``/``spare`` are the exact GLOBAL accounting of
        `_reservation`.  When the pool spans devices and the job fits on
        one (W <= slots-per-device), the reservation additionally pins
        ``d_star`` — the device provably able to host the job WHOLE at
        ``start`` (free slots now plus slots its running jobs retire by
        then) — and ``spare_dev``, d_star's start-time surplus beyond W.
        Condition-(b) backfill must keep that surplus intact: counting
        freed slots only globally lets a narrow admit occupy d_star past
        ``start`` and silently demote the wide job's single-device start
        to a spanning one (the placement bug this method fixes).
        """
        start, spare = self._reservation(job, planner.total_free, running)
        d_star = spare_dev = None
        W = job.num_slots
        # Per-device protection only matters when placement is affine:
        # a flat pool ignores devices, so guarding one would change
        # admission timing for nothing in return.
        if (
            planner.devices > 1
            and planner.mode == "affine"
            and W <= planner.cap
        ):
            avail = planner.free_by_device()
            for j in running:
                if j.total_remaining() <= start:
                    for b in planner.slots_of(j):
                        avail[planner.device_of(b)] += 1
            # Only devices that can hold W at all are candidates (an
            # uneven pool may have devices smaller than the job); rank
            # by RELATIVE projected availability so a half-empty small
            # device does not outbid a nearly-empty big one.
            caps = planner.capacities
            feas = [d for d in range(planner.devices) if caps[d] >= W]
            if feas:
                best = max(
                    feas, key=lambda d: (avail[d] / caps[d], avail[d], -d)
                )
                if avail[best] >= W:
                    d_star, spare_dev = best, avail[best] - W
        return start, spare, d_star, spare_dev

    def _pick_victims(self, job, running: list, free: int) -> list | None:
        """Lowest-priority active jobs to evict so ``job`` fits, or None
        if even evicting every lower-priority job would not suffice."""
        need = job.num_slots - free
        cands = sorted(
            (v for v in running if v.priority < job.priority),
            key=lambda v: (v.priority, -v.num_slots, v.jid),
        )
        take: list = []
        got = 0
        for v in cands:
            take.append(v)
            got += v.num_slots
            if got >= need:
                break
        if got < need:
            return None
        # Trim overshoot: drop any victim whose slots we don't need
        # (smallest first), so preemption evicts the minimum set.
        for v in sorted(take, key=lambda v: (v.num_slots, -v.priority)):
            if got - v.num_slots >= need:
                take.remove(v)
                got -= v.num_slots
        return take

    def plan(self, free, active: list) -> tuple[list, list]:
        legacy = not isinstance(free, PlacementPlanner)
        planner = (
            PlacementPlanner.from_counts(free, active) if legacy else free
        )
        preempt: list = []
        admit: list = []  # (job, slots) pairs
        running = list(active)  # original actives + planned admissions
        originals = set(id(j) for j in active)
        reservation = None  # (start, spare, d_star, spare_dev) of blocked job
        for job in self._order():
            n = job.num_slots
            if reservation is None:
                if n <= planner.total_free:
                    admit.append((job, planner.alloc(job)))
                    self._charge(job)
                    running.append(job)
                    continue
                if self.preempt:
                    victims = self._pick_victims(
                        job,
                        [v for v in running if id(v) in originals],
                        planner.total_free,
                    )
                    if victims is not None:
                        for v in victims:
                            preempt.append(v)
                            running.remove(v)
                            originals.discard(id(v))
                            planner.release_job(v)
                        admit.append((job, planner.alloc(job)))
                        self._charge(job)
                        running.append(job)
                        continue
                if not self.backfill:
                    break
                reservation = self._reservation_placed(job, planner, running)
                continue
            # Backfill under the reservation: exact no-delay accounting.
            start, spare, d_star, spare_dev = reservation
            if n <= planner.total_free and job.total_remaining() <= start:
                # Retires before the reserved start: its slots (wherever
                # placed) are back by then, so it cannot erode the
                # reservation globally OR on d_star.
                admit.append((job, planner.alloc(job)))
                self._charge(job)
                running.append(job)
            elif n <= planner.total_free and n <= spare:
                # Fits the slots the reserved job spares — but only if it
                # also leaves d_star's start-time surplus intact, else a
                # narrow admit would force the wide job to span devices.
                slots = planner.alloc(job, avoid=d_star)
                if d_star is not None:
                    on_star = sum(
                        1 for b in slots if planner.device_of(b) == d_star
                    )
                    if on_star > spare_dev:
                        planner.putback(job)
                        continue
                    spare_dev -= on_star
                admit.append((job, slots))
                self._charge(job)
                running.append(job)
                reservation = (start, spare - n, d_star, spare_dev)
        for job, _ in admit:
            self._queued.remove(job)
        for job in preempt:
            # Evicted jobs go back in the queue under their ORIGINAL
            # submission seq, so they re-sort ahead of later arrivals of
            # the same priority/user and resume as soon as slots free up.
            self.enqueue(job)
        if legacy:
            return preempt, [job for job, _ in admit]
        return preempt, admit


def make_policy(policy, user_weights=None, aging_sweeps=0) -> AdmissionPolicy:
    """``"fifo"`` | ``"backfill"`` | ``"fair"`` | an `AdmissionPolicy`."""
    if isinstance(policy, AdmissionPolicy):
        return policy
    if policy == "fifo":
        if user_weights:
            raise ValueError("user_weights only apply to policy='fair'")
        if aging_sweeps:
            raise ValueError(
                "aging_sweeps applies to the priority policies "
                "('backfill'/'fair'); FIFO has no priorities to age"
            )
        return AdmissionPolicy()
    if policy == "backfill":
        return PriorityBackfillPolicy(
            fair=False, user_weights=user_weights, aging_sweeps=aging_sweeps
        )
    if policy == "fair":
        return PriorityBackfillPolicy(
            fair=True, user_weights=user_weights, aging_sweeps=aging_sweeps
        )
    raise ValueError(
        f"unknown policy {policy!r}; choose 'fifo', 'backfill', 'fair' or "
        "pass an AdmissionPolicy instance"
    )


class AdaptiveChunker:
    """Chunk-size policy: launch-cost EWMA + queue depth -> menu chunk.

    ``chunk_sweeps="adaptive"`` replaces the static knob (ROADMAP
    "Adaptive chunk sizing").  Two pressures trade off: bigger chunks
    amortize per-launch overhead (throughput), smaller chunks reach
    admit/retire points sooner so queued jobs start earlier (latency).
    The policy measures the per-sweep launch cost as an EWMA and sizes
    the next chunk to a target launch wall time, shrunk by the current
    queue depth; the result is floored to a fixed power-of-two MENU so
    the engine's per-``num_sweeps`` jit cache stays bounded by
    ``len(menu)`` entries no matter how traffic fluctuates (chunks are
    additionally capped at segment boundaries, and every such clamp is
    floored to the menu too — 1 is always a member).

    Chunk size never changes results (DESIGN.md §Service determinism
    contract), so adapting it on wall-clock measurements is safe.

    An instance holds per-engine state (the EWMA and the set of
    already-compiled chunk sizes): give each `SampleServer` its OWN
    chunker — sharing one across servers would treat the second server's
    compiles as warm launches and poison the EWMA.
    """

    def __init__(
        self,
        target_launch_s: float = 0.05,
        max_chunk: int = 64,
        init_chunk: int = 8,
        alpha: float = 0.3,
    ):
        if max_chunk < 1:
            raise ValueError(f"max_chunk must be >= 1, got {max_chunk}")
        menu = [1]
        while menu[-1] * 2 <= max_chunk:
            menu.append(menu[-1] * 2)
        self.menu = tuple(menu)
        self.target_launch_s = float(target_launch_s)
        self.init_chunk = int(init_chunk)
        self.alpha = float(alpha)
        self.per_sweep_ewma: float | None = None
        self._warm: set[int] = set()  # chunk sizes whose jit is compiled

    def floor_to_menu(self, k: int) -> int:
        """Largest menu chunk <= max(1, k)."""
        k = max(1, int(k))
        out = 1
        for c in self.menu:
            if c <= k:
                out = c
        return out

    def propose(self, queue_depth: int, segment_bound: int) -> int:
        """Next chunk: cost-targeted, queue-shrunk, boundary-capped."""
        if self.per_sweep_ewma is None or self.per_sweep_ewma <= 0.0:
            desired = float(self.init_chunk)
        else:
            desired = self.target_launch_s / self.per_sweep_ewma
        desired = desired / (1 + queue_depth)
        return self.floor_to_menu(int(min(desired, segment_bound)))

    def observe(self, chunk: int, launch_s: float) -> None:
        if chunk not in self._warm:
            # First launch at a chunk size pays one-time trace+compile
            # (num_sweeps is a static jit arg) — orders of magnitude above
            # steady state; recording it would collapse the policy to
            # chunk=1 for the whole warm-up ramp.  Discard it.
            self._warm.add(chunk)
            return
        per_sweep = launch_s / max(1, chunk)
        if self.per_sweep_ewma is None:
            self.per_sweep_ewma = per_sweep
        else:
            self.per_sweep_ewma += self.alpha * (per_sweep - self.per_sweep_ewma)


class ProfilerError(RuntimeError):
    """An armed profiler trace failed to start or stop.

    ``results`` holds the jobs that retired in the `step` or `drain` call
    that raised it: they have left the server, so this is the caller's
    only copy of them.
    """

    def __init__(self, message: str, results=()):
        super().__init__(message)
        self.results: List[JobResult] = list(results)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Every `SampleServer` construction knob as one value object.

    The server's constructor had grown a kwarg per subsystem (engine
    shape, scheduling policy, placement, telemetry, crash safety, ...);
    a config object makes the full shape nameable — snapshots persist
    it, `restore_server` rebuilds from it, and call sites can share or
    tweak one config (`dataclasses.replace`) instead of re-threading a
    dozen kwargs.  ``SampleServer(model, config=cfg)`` and the historical
    ``SampleServer(model, slots=8, ...)`` are the same thing: bare
    kwargs are folded into the config (kwargs win over a config's field
    when both are given).  Field semantics are documented on
    `SampleServer`; defaults here ARE the server's defaults.
    """

    slots: int = 8
    chunk_sweeps: int | str = 8
    rung: str = "cb"
    backend: str = "jnp"
    V: int = 4
    exp_flavor: str | None = None
    interpret: bool | None = None
    replica_tile: int | None = None
    idle_seed: int = 0
    chunker: "AdaptiveChunker | None" = None
    multi_tenant: bool = False
    policy: object = "fair"
    user_weights: dict | None = None
    aging_sweeps: int = 0
    wait_window: int = 256
    mesh: object = None
    placement: str = "affine"
    capacities: tuple | None = None
    telemetry: object = True
    stream: "ObservableStream | None" = None
    snapshot_manager: object = None
    snapshot_every_sweeps: int = 0
    preemption: object = None


class SampleServer:
    """Schedules a queue of jobs onto the batch dim of one engine.

    Construction: ``SampleServer(model, config=ServeConfig(...))`` or
    the historical bare kwargs (``SampleServer(model, slots=8, ...)``)
    — kwargs are folded into the config, overriding its fields, and the
    merged config is kept as ``self.config`` (snapshots persist the
    construction shape from it).
    """

    def __init__(
        self,
        model: ising.LayeredModel,
        *,
        config: ServeConfig | None = None,
        **kwargs,
    ):
        if config is None:
            cfg = ServeConfig(**kwargs)  # TypeError names unknown kwargs
        elif kwargs:
            cfg = dataclasses.replace(config, **kwargs)
        else:
            cfg = config
        self.config = cfg
        slots = cfg.slots
        chunk_sweeps = cfg.chunk_sweeps
        rung, backend, V = cfg.rung, cfg.backend, cfg.V
        exp_flavor, interpret = cfg.exp_flavor, cfg.interpret
        replica_tile, idle_seed = cfg.replica_tile, cfg.idle_seed
        chunker, multi_tenant = cfg.chunker, cfg.multi_tenant
        policy, user_weights = cfg.policy, cfg.user_weights
        aging_sweeps, wait_window = cfg.aging_sweeps, cfg.wait_window
        mesh, placement = cfg.mesh, cfg.placement
        telemetry, stream = cfg.telemetry, cfg.stream
        snapshot_manager = cfg.snapshot_manager
        snapshot_every_sweeps = cfg.snapshot_every_sweeps
        preemption = cfg.preemption
        if chunk_sweeps == "adaptive":
            self._chunker = chunker or AdaptiveChunker()
        elif isinstance(chunk_sweeps, str):
            raise ValueError(
                f"chunk_sweeps must be an int >= 1 or 'adaptive', got {chunk_sweeps!r}"
            )
        elif chunk_sweeps < 1:
            raise ValueError(f"chunk_sweeps must be >= 1, got {chunk_sweeps}")
        else:
            self._chunker = None
        if backend == "pallas":
            from repro.kernels import ops  # deferred: kernels are optional

            V = ops.LANES
        self.multi_tenant = bool(multi_tenant)
        # One constructor path for both tenancy shapes: a multi-tenant
        # server starts every slot on the base model (jobs carrying their
        # own model get its coupling tables spliced in at admission).
        self.engine = SweepEngine.create(
            [model] * slots if self.multi_tenant else model,
            rung=rung,
            backend=backend,
            batch=None if self.multi_tenant else slots,
            V=V,
            exp_flavor=exp_flavor,
            interpret=interpret,
            replica_tile=replica_tile,
            mesh=mesh,
            capacities=cfg.capacities,
        )
        # Idle slots hold (and keep sweeping) this placeholder state until
        # a job is spliced over it.
        self.carry = self.engine.init_carry(seed=idle_seed)
        self.chunk_sweeps = None if self._chunker else int(chunk_sweeps)
        self.policy = make_policy(policy, user_weights, aging_sweeps)
        self._active: dict[int, tuple] = {}  # jid -> (job, slots tuple)
        self._next_jid = 0
        # The one metrics registry: stats(), the Prometheus/JSON exporters
        # and the Chrome trace all read it, so their numbers cannot
        # disagree.  telemetry=False only silences EVENT recording —
        # counters keep counting because stats() is built on them.
        self.telemetry = (
            telemetry
            if isinstance(telemetry, Telemetry)
            else Telemetry(enabled=bool(telemetry))
        )
        self.telemetry.name_thread(0, "scheduler")
        tel = self.telemetry
        self.engine.telemetry = tel  # the engine's spans join the server's
        self._c_launches = tel.counter("serve.launches")
        # the global sweep clock (sum of chunks), read via .sweeps_elapsed
        self._c_sweeps = tel.counter("serve.sweeps_elapsed")
        self._c_busy = tel.counter("serve.busy_slot_sweeps")
        self._c_total = tel.counter("serve.total_slot_sweeps")
        self._c_preempt = tel.counter("serve.preemptions")
        self._c_submitted = tel.counter("serve.jobs_submitted")
        self._c_completed = tel.counter("serve.jobs_completed")
        self._c_straggler = tel.counter("serve.straggler_events")
        self._h_wait = tel.histogram("serve.queue_wait_s")
        # Placement decisions and PT swap routing (DESIGN.md §Scheduling/
        # Placement): affine = all of a job's slots on one device;
        # every PT swap phase counts once, as swap_local (the one-call
        # in-device path) or swap_cross (a ladder spanning devices).
        self._c_place_affine = tel.counter("sched.placements_affine")
        self._c_place_span = tel.counter("sched.placements_spanning")
        self._c_migrations = tel.counter("sched.rebalance_migrations")
        self._c_swap_local = tel.counter("pt.swap_local")
        self._c_swap_cross = tel.counter("pt.swap_cross")
        # Tau rows and betas the step's hooks handed over (`queue_tau`,
        # `queue_beta`), written after the hook loop by one
        # `engine.set_slot_tau` and one `engine.set_slot_betas` call.
        self._due_tau: list = []
        self._c_tau_rows = tel.counter("sqa.tau_rows")
        self._due_betas: list = []
        self._c_beta_rows = tel.counter("anneal.beta_rows")
        self.stream = stream
        # Chunk sizes already compiled (num_sweeps is a static jit arg):
        # a launch whose size is not in here pays compilation, and its
        # trace event says so (compile=True).
        self._warm_chunks: set[int] = set()
        self.devices = self.engine.mesh.shape["data"] if mesh is not None else 1
        # The slot pool: free lists keyed by device over the mesh's
        # contiguous per-device blocks (equal B/D split, or the explicit
        # ``capacities`` vector on a heterogeneous mesh).  placement=
        # "affine" packs multi-slot jobs onto one device when possible
        # (PT swaps stay on the in-device fast path); "flat" is the
        # historical single-list order.  Placement never changes
        # results, only locality.
        self._pool = SlotPool(
            self.slots,
            devices=self.devices,
            mode=placement,
            capacities=self.engine.capacities if mesh is not None else None,
        )
        self._skew = (
            LaunchSkewMonitor(self.devices) if self.devices > 1 else None
        )
        self._profiler: dict | None = None
        # Queue-wait samples (user, priority, wait_s, wait_sweeps), taken
        # at FIRST admission; bounded so a resident server never grows it
        # without limit.
        self._wait_records: deque = deque(maxlen=100_000)
        # Rolling window over the last ``wait_window`` admissions — the
        # recency-weighted SLO signal (`stats()["queue_wait_recent"]`) a
        # resident server alerts on, robust against the since-start
        # aggregates flattening out over a long uptime.
        if wait_window < 1:
            raise ValueError(f"wait_window must be >= 1, got {wait_window}")
        self._wait_recent: deque = deque(maxlen=int(wait_window))
        # Crash safety (DESIGN.md §Recovery): an optional CheckpointManager
        # (or directory path) for whole-server snapshots.  With
        # ``snapshot_every_sweeps=K`` the server snapshots itself every K
        # sweeps of its clock, at the step boundary, via the manager's
        # non-blocking writer; `snapshot()` can also be called explicitly.
        # ``preemption`` (a `runtime.ft.PreemptionHandler`) arms graceful
        # drain: `drain()` checks it between chunks and, when triggered,
        # snapshots and returns early with ``self.preempted`` set.
        if isinstance(snapshot_manager, str):
            from repro.ckpt.manager import CheckpointManager

            snapshot_manager = CheckpointManager(snapshot_manager)
        self.snapshot_manager = snapshot_manager
        if snapshot_every_sweeps < 0:
            raise ValueError(
                f"snapshot_every_sweeps must be >= 0, got {snapshot_every_sweeps}"
            )
        if snapshot_every_sweeps and snapshot_manager is None:
            raise ValueError(
                "snapshot_every_sweeps needs a snapshot_manager (or directory)"
            )
        self.snapshot_every_sweeps = int(snapshot_every_sweeps)
        self.preemption = preemption
        self.preempted = False
        self._last_snapshot_sweep = 0
        # Retirement log (jids in retirement order), bounded like the wait
        # ring; snapshots persist it so a restored run's combined
        # retirement order can be audited against an uninterrupted one.
        self._retired: deque = deque(maxlen=100_000)

    # -- submission -----------------------------------------------------------

    @property
    def slots(self) -> int:
        return self.engine.batch

    @property
    def num_active(self) -> int:
        return len(self._active)

    @property
    def num_queued(self) -> int:
        return len(self.policy)

    # Throughput counters live in the telemetry registry (the one source
    # stats() and the exporters share); these properties keep the
    # original attribute API for tests, benches and examples.

    @property
    def launches(self) -> int:
        return self._c_launches.value

    @property
    def busy_slot_sweeps(self) -> int:
        return self._c_busy.value

    @property
    def total_slot_sweeps(self) -> int:
        return self._c_total.value

    @property
    def sweeps_elapsed(self) -> int:
        return self._c_sweeps.value

    @property
    def preemptions(self) -> int:
        return self._c_preempt.value

    @property
    def launch_chunks(self) -> Counter:
        """chunk size -> launch count, rebuilt from the labeled counter
        series (a Counter, not a log: a resident server launches forever)."""
        return Counter(
            {
                int(labels["chunk"]): int(value)
                for labels, value in self.telemetry.series(
                    "serve.launches_by_chunk"
                )
            }
        )

    def submit(self, job) -> int:
        """Enqueue a job; returns its assigned job id."""
        if job.num_slots > self.slots:
            raise ValueError(
                f"job needs {job.num_slots} slots, server has {self.slots}"
            )
        if job.jid is not None:
            raise ValueError(f"job already submitted (jid={job.jid})")
        if job.rewrites_tables and not self.multi_tenant:
            raise ValueError(
                f"{type(job).__name__} rewrites its slots' coupling tables "
                "as it runs; this server is single-model — construct it "
                "with multi_tenant=True"
            )
        if getattr(job, "model", None) is not None:
            if not self.multi_tenant:
                raise ValueError(
                    "job carries its own model; this server is single-model "
                    "— construct it with multi_tenant=True"
                )
            self.engine.check_model(job.model)  # reject topology mismatch now
        job.jid = self._next_jid
        self._next_jid += 1
        job._submit_time = time.perf_counter()
        job._submit_sweep = self.sweeps_elapsed
        job._admit_time = None
        self.policy.enqueue(job)
        self._c_submitted.add(1)
        self.telemetry.async_begin(
            "job",
            job.jid,
            kind=job.kind,
            slots=job.num_slots,
            priority=job.priority,
            user=job.user,
            submit_sweep=job._submit_sweep,
        )
        return job.jid

    # -- scheduling -----------------------------------------------------------

    def _admit(self) -> None:
        """One planning round: the policy decides, the server executes.

        Every call happens between launches, i.e. at a chunk boundary —
        the only point where preemption is safe (slot state is a complete
        checkpoint there) and where admission keeps the determinism
        contract (the RNG stream position is a pure function of sweeps
        completed, so WHEN a slot is filled never changes what it
        computes).
        """
        # Refresh the policy's sweep clock first: priority aging reads it
        # to compute how long each queued job has waited.
        self.policy.clock = self.sweeps_elapsed
        if self._pool.mode == "affine" and self.devices > 1:
            self._rebalance()
        # The policy plans against a PRIVATE clone of the pool (plus the
        # active jobs' placements); the real pool only mutates below,
        # when the server executes the plan.
        planner = PlacementPlanner(
            self._pool,
            {id(j): slots for j, slots in self._active.values()},
        )
        free_before = planner.total_free
        preempts, admits = self.policy.plan(
            planner, [j for j, _ in self._active.values()]
        )
        # Built-in policies return (job, slots) placements; custom
        # policies may still return bare jobs — the server places those.
        admits = [e if isinstance(e, tuple) else (e, None) for e in admits]
        if preempts or admits:
            self.telemetry.instant(
                "sched.plan",
                policy=self.policy.name,
                free=free_before,
                queued=len(self.policy),
                admitted=[j.jid for j, _ in admits],
                preempted=[j.jid for j in preempts],
            )
        for job in preempts:
            self._park(job)
        for job, slots in admits:
            self._place(job, slots)

    def queue_tau(self, slot: int, tau_row) -> None:
        """Hand over slot ``slot``'s next tau couplings (an (n,) row) from
        a job hook; the step writes every row handed over in one
        `engine.set_slot_tau` call after its hook loop, before the next
        launch."""
        self._due_tau.append((int(slot), tau_row))

    def queue_beta(self, slot: int, beta: float) -> None:
        """Hand over slot ``slot``'s next beta from a job hook; the step
        writes every beta handed over in one `engine.set_slot_betas`
        call after its hook loop, before the next launch."""
        self._due_betas.append((int(slot), beta))

    def _park(self, job) -> None:
        """Checkpoint-preempt an active job: extract each slot's carry
        (and coupling tables) into the job's ``parked`` list and free the
        slots.  The policy has already re-queued the job; re-admission
        resumes it bit-exactly (`_place`)."""
        _, taken = self._active.pop(job.jid)
        job.parked = [self.engine.slot(b).park(self.carry) for b in taken]
        job.preemptions += 1
        self._c_preempt.add(1)
        self._pool.release_all(taken)  # raises on double-free
        self.telemetry.async_instant(
            "job",
            job.jid,
            phase="park",
            reason="preempt",
            sweeps_done=job.sweeps_done,
        )

    def _place(self, job, placement=None) -> None:
        """Splice a job into free slots: fresh init on first admission,
        parked-state resume after a preemption.  ``placement`` is the
        policy's planned slots; ``None`` (custom policies returning bare
        jobs) lets the server's own pool place the job."""
        if placement is None:
            if job.num_slots > self._pool.total_free:
                # Guard the public policy extension point: an over-admitting
                # plan() must fail loudly, not truncate the job's slots (a
                # short slots tuple would silently corrupt multi-slot jobs).
                raise RuntimeError(
                    f"policy {self.policy.name!r} admitted job {job.jid} needing "
                    f"{job.num_slots} slots with only {self._pool.total_free} free"
                )
            taken = self._pool.alloc(job.num_slots)
        else:
            taken = tuple(int(b) for b in placement)
            self._pool.take(taken)  # raises if the plan double-booked a slot
        devs = sorted({self._pool.device_of(b) for b in taken})
        if self.devices > 1:
            affine = len(devs) == 1
            (self._c_place_affine if affine else self._c_place_span).add(1)
            self.telemetry.instant(
                "sched.placement",
                jid=job.jid,
                slots=list(taken),
                devices=devs,
                affine=affine,
                mode=self._pool.mode,
            )
        tel = self.telemetry
        if job.parked is not None:
            model = job.model_on(self) if self.multi_tenant else None
            with tel.span("engine.splice", cat="engine"):
                for b, parked in zip(taken, job.parked):
                    self.carry = self.engine.slot(b).resume(
                        self.carry, parked, model=model
                    )
            job.parked = None
        else:
            with tel.span("job.init"):
                slot_carries = job.init_carries(self)
            with tel.span("engine.splice", cat="engine"):
                for b, slot_carry in zip(taken, slot_carries):
                    if self.multi_tenant:
                        # The slot sweeps the job's model from now on:
                        # splice its coupling tables next to the carry
                        # (jobs without a model reset the slot to the base
                        # model, so a retired tenant's tables never leak
                        # into the next job).
                        self.engine.set_slot_model(b, job.model_on(self))
                    self.carry = self.engine.slot(b).splice(self.carry, slot_carry)
        if job._admit_time is None:
            job._admit_time = time.perf_counter()
            job._admit_sweep = self.sweeps_elapsed
            wait_s = job._admit_time - job._submit_time
            wait_sweeps = self.sweeps_elapsed - job._submit_sweep
            self._wait_records.append((job.user, job.priority, wait_s, wait_sweeps))
            self._wait_recent.append((wait_s, wait_sweeps))
            self._h_wait.observe(wait_s)
            self.telemetry.async_instant(
                "job",
                job.jid,
                phase="admit",
                slots=list(taken),
                wait_s=wait_s,
                wait_sweeps=wait_sweeps,
            )
        else:
            self.telemetry.async_instant(
                "job",
                job.jid,
                phase="resume",
                slots=list(taken),
                sweeps_done=job.sweeps_done,
            )
        self._active[job.jid] = (job, taken)

    def _rebalance(self) -> None:
        """Chunk-boundary defragmentation (affine mode, ``devices > 1``).

        When a queued multi-slot job would fit one device (W no wider
        than the largest per-device capacity) and fits the pool
        globally, but fragmentation leaves no single device with W free,
        migrate active slots OFF the relatively-most-free device that
        can hold W until it can host the job whole.  Each migration is a
        park+resume pair —
        position- and device-independent bit-exact (DESIGN.md §Recovery) —
        so rebalancing changes placement, never results.  Invariants: the
        total free count is unchanged (one release per alloc); migrations
        happen only at the chunk boundary (the same safety point as
        preemption); a migrated slot never lands back on the target
        device (the loop stops if fragmentation leaves nowhere else).
        """
        pool = self._pool
        target = None
        for job in self.policy.jobs():
            W = job.num_slots
            if (
                1 < W <= pool.cap
                and W <= pool.total_free
                and max(pool.free_by_device()) < W
            ):
                target = job
                break
        if target is None:
            return
        free_by = pool.free_by_device()
        caps = pool.capacities
        # Migration target: relatively-emptiest device big enough to
        # host the job whole (absolute free, then lowest index, as ties).
        feas = [d for d in range(self.devices) if caps[d] >= target.num_slots]
        if not feas:
            return
        d_t = max(
            feas,
            key=lambda d: (
                free_by[d] / caps[d] if caps[d] else 0.0,
                free_by[d],
                -d,
            ),
        )
        need = target.num_slots - free_by[d_t]
        if need > pool.total_free - free_by[d_t]:
            return  # nowhere else to absorb the displaced slots
        # Occupied slots on the target device, preferring single-slot
        # jobs (moving one rung of a resident ladder would split it) and
        # higher indices (displaced state re-packs lowest-first).
        occupants = []
        for jid, (job, slots) in self._active.items():
            for i, b in enumerate(slots):
                if pool.device_of(b) == d_t:
                    occupants.append((job.num_slots != 1, -b, jid, i, b))
        occupants.sort()
        moved = 0
        for _, _, jid, i, b_src in occupants:
            if moved >= need:
                break
            job, slots = self._active[jid]
            (b_dst,) = pool.alloc(1, avoid=d_t)
            if pool.device_of(b_dst) == d_t:
                pool.release(b_dst)  # only d_t itself had room: stop
                break
            parked = self.engine.slot(b_src).park(self.carry)
            model = job.model_on(self) if self.multi_tenant else None
            self.carry = self.engine.slot(b_dst).resume(
                self.carry, parked, model=model
            )
            new_slots = list(slots)
            new_slots[i] = b_dst
            self._active[jid] = (job, tuple(new_slots))
            pool.release(b_src)
            moved += 1
            self._c_migrations.add(1)
            self.telemetry.async_instant(
                "job",
                jid,
                phase="migrate",
                src=int(b_src),
                dst=int(b_dst),
                reason=f"defrag_device_{d_t}",
            )
        if moved:
            self.telemetry.instant(
                "sched.rebalance",
                device=d_t,
                migrated=moved,
                for_jid=target.jid,
                free_by_device=pool.free_by_device(),
            )

    def arm_profiler(self, logdir: str, num_chunks: int = 4) -> None:
        """Arm a `jax.profiler` trace window around the next
        ``num_chunks`` engine launches: the device-level profile (HLO
        ops, fusion, memory — TensorBoard/Perfetto-loadable under
        ``logdir``) that the host-side Chrome trace cannot see.  The
        window opens right before the next launch and closes after the
        Nth.  A start or stop that fails disarms the window and raises
        `ProfilerError` from `step` and `drain`: a profile that was asked
        for and not taken is an error.  The window opens after admission
        and closes at the end of a step, so the server's state stays
        consistent, the error carries the jobs retired so far, and a
        later `step` or `drain` carries on."""
        if num_chunks < 1:
            raise ValueError(f"num_chunks must be >= 1, got {num_chunks}")
        self._profiler = {
            "logdir": str(logdir),
            "remaining": int(num_chunks),
            "active": False,
        }

    def _launch(self, chunk: int) -> None:
        """Dispatch one fused engine launch; return the pending probe.

        Timing forces completion (`block_until_ready`) — under JAX's
        async dispatch, timing the dispatch alone measures nothing.  But
        blocking *immediately* after dispatch would also serialize the
        device against the step's Python bookkeeping, which the
        fire-and-forget path overlaps for free.  So the launch is split:
        this method dispatches and returns `(t0, compiled)` when timing
        is wanted, and `_settle_launch` blocks/records later — after
        `step()` has done its pure-Python work in the shadow of the
        device compute.  With a fixed chunk and event recording off,
        the launch stays fire-and-forget (`None` pending): the
        telemetry-off path IS the pre-observability hot path (the
        overhead bench compares the two).
        """
        tel = self.telemetry
        prof = self._profiler
        if prof is not None and not prof["active"]:
            self._profiler = None  # a failed start leaves nothing armed
            try:
                jax.profiler.start_trace(prof["logdir"])
            except Exception as e:
                raise ProfilerError(f"profiler trace did not start: {e}") from e
            prof["active"] = True
            self._profiler = prof
            tel.instant("profiler.start", logdir=prof["logdir"])
        timed = self._chunker is not None or tel.enabled
        pending = None
        if not timed:
            self.carry = self.engine.run(self.carry, chunk)
        else:
            compiled = chunk in self._warm_chunks
            t0 = time.perf_counter()
            with tel.span("engine.dispatch", cat="engine"):
                self.carry = self.engine.run(self.carry, chunk)
            pending = (t0, compiled)
        self._warm_chunks.add(chunk)
        self._c_launches.add(1)
        tel.counter("serve.launches_by_chunk", chunk=chunk).add(1)
        self._c_sweeps.add(chunk)
        return pending

    def _settle_launch(self, chunk: int, pending) -> None:
        """Force the dispatched launch to completion and record timing.

        `dt` spans dispatch start -> device ready.  If the device
        finished while `step()` was still doing Python bookkeeping, the
        block returns immediately and `dt` absorbs (at most) that
        bookkeeping time — a sub-millisecond ceiling that buys back the
        dispatch/compute overlap, which is worth far more than the bias.
        On a sharded engine the probe times each device's shard instead
        (`device_ready_times`) and feeds the skew monitor, so one
        straggling device is flagged, not averaged into the wall time.
        """
        tel = self.telemetry
        if pending is not None:
            t0, compiled = pending
            if self._skew is not None and tel.enabled:
                with tel.span("engine.wait", cat="engine"):
                    times = self.engine.device_ready_times(self.carry, t0)
                dt = float(times.max())
                flagged = self._skew.record(times)
                if flagged:
                    self._c_straggler.add(len(flagged))
                    tel.instant(
                        "engine.straggler",
                        cat="engine",
                        devices=flagged,
                        times_s=[float(t) for t in times],
                    )
            else:
                with tel.span("engine.wait", cat="engine"):
                    jax.block_until_ready(self.carry)
                dt = time.perf_counter() - t0
            if self._chunker is not None:
                self._chunker.observe(chunk, dt)
            phase = "steady" if compiled else "compile"
            tel.histogram("serve.launch_s", phase=phase).observe(dt)
            tel.complete(
                "engine.launch",
                dur_us=dt * 1e6,
                cat="engine",
                chunk=chunk,
                jobs=len(self._active),
                devices=self.devices,
                compile=not compiled,
            )
        if self._profiler is not None and self._profiler["active"]:
            self._profiler["remaining"] -= 1

    def _close_profiler_window(self, completed: List[JobResult]) -> None:
        """Stop an armed profiler trace whose window of launches is
        spent; runs at the end of a step, so a failure raises with the
        step's bookkeeping already complete and its retired jobs
        (``completed``) in the error."""
        prof = self._profiler
        if prof is not None and prof["active"] and prof["remaining"] <= 0:
            self._profiler = None
            try:
                jax.profiler.stop_trace()
            except Exception as e:
                raise ProfilerError(
                    f"profiler trace did not stop: {e}", completed
                ) from e
            self.telemetry.instant("profiler.stop")

    def step(self) -> List[JobResult]:
        """One scheduling round: admit, one chunked launch, hooks, retire.

        Returns the jobs that retired this round (possibly empty).
        """
        tel = self.telemetry
        with tel.span("sched.step"):
            with tel.span("sched.admit"):
                self._admit()
            tel.gauge("serve.active_jobs").set(len(self._active))
            tel.gauge("serve.queued_jobs").set(len(self.policy))
            tel.gauge("serve.free_slots").set(self._pool.total_free)
            if self.devices > 1:
                for d, nfree in enumerate(self._pool.free_by_device()):
                    tel.gauge("serve.free_slots_dev", device=d).set(nfree)
            if not self._active:
                return []
            bound = min(
                j.remaining_in_segment() for j, _ in self._active.values()
            )
            if self._chunker is not None:
                chunk = self._chunker.propose(len(self.policy), bound)
            else:
                chunk = min(self.chunk_sweeps, bound)
            pending = self._launch(chunk)
            # Pure-Python bookkeeping runs in the shadow of the device
            # compute (the launch above is dispatched, not yet forced).
            busy = sum(j.num_slots for j, _ in self._active.values())
            self._c_busy.add(chunk * busy)
            self._c_total.add(chunk * self.slots)
            # Advance all jobs first, THEN tap the stream: sweeps_done is
            # current and a retiring job's final chunk is still sampled
            # (hooks rewrite betas and coupling tables, never spins, so
            # pre-hook spins are the post-chunk spins).
            boundary = [
                jid
                for jid in list(self._active)
                if self._active[jid][0].advance(chunk)
            ]
            self._settle_launch(chunk, pending)
            if self.stream is not None:
                self.stream.record(self)
            completed: List[JobResult] = []
            for jid in boundary:
                job, taken = self._active[jid]
                self.carry = job.on_segment(self, self.carry, taken)
                if job.done:
                    with tel.span("job.finalize"):
                        completed.append(job.finalize(self, taken))
                    self._pool.release_all(taken)  # raises on double-free
                    del self._active[jid]
                    self._retired.append(jid)
                    self._c_completed.add(1)
                    tel.async_end(
                        "job",
                        jid,
                        sweeps_done=job.sweeps_done,
                        chunks=job.chunks,
                        preemptions=job.preemptions,
                    )
            if self._due_tau:
                # Every tau row the hooks handed over, in one engine call
                # (one executable, whatever their number).
                slots, rows = zip(*self._due_tau)
                self._due_tau = []
                self.carry = self.engine.set_slot_tau(self.carry, slots, rows)
                self._c_tau_rows.add(len(slots))
            if self._due_betas:
                slots, betas = zip(*self._due_betas)
                self._due_betas = []
                self.carry = self.engine.set_slot_betas(self.carry, slots, betas)
                self._c_beta_rows.add(len(slots))
            if (
                self.snapshot_every_sweeps
                and self.sweeps_elapsed - self._last_snapshot_sweep
                >= self.snapshot_every_sweeps
            ):
                # Periodic background snapshot at the step boundary: the
                # pool gather is synchronous (it must see THIS boundary),
                # the fsync'd writes ride the manager's writer thread.
                self.snapshot(blocking=False)
            self._close_profiler_window(completed)
        return completed

    def drain(self, max_steps: int = 1_000_000) -> List[JobResult]:
        """Run scheduling rounds until queue and slots are empty.

        With a ``preemption`` handler armed, a triggered handler (SIGTERM
        in production, `trigger()` in tests) is honoured between chunks:
        the in-flight chunk finishes — chunk boundaries are the only
        consistent checkpoint — then the server snapshots (blocking, so
        the snapshot is durable before the process exits) and returns the
        results retired so far with ``self.preempted`` set.  A later
        `SampleServer.restore` continues the remaining work bit-exactly.
        """
        results: List[JobResult] = []
        for _ in range(max_steps):
            if not len(self.policy) and not self._active:
                self.wait_snapshots()  # no dangling writer past a drain
                return results
            if self.preemption is not None and self.preemption.should_exit:
                self.telemetry.instant(
                    "sched.preempt_drain",
                    queued=len(self.policy),
                    active=len(self._active),
                    sweeps_elapsed=self.sweeps_elapsed,
                )
                if self.snapshot_manager is not None:
                    self.snapshot(blocking=True)
                self.preempted = True
                return results
            try:
                results.extend(self.step())
            except ProfilerError as e:
                e.results[:0] = results
                raise
        raise RuntimeError(f"drain did not converge in {max_steps} steps")

    # -- snapshot / restore (serve_mc/snapshot.py; DESIGN.md §Recovery) -------

    def wait_snapshots(self) -> None:
        """Join any in-flight background snapshot write (durability point:
        after this returns, the newest snapshot is fully on disk)."""
        if self.snapshot_manager is not None:
            self.snapshot_manager.wait()

    def snapshot(self, manager=None, *, step: int | None = None,
                 blocking: bool = True) -> int:
        """Write a whole-server snapshot; returns its step number.

        Call between scheduling rounds (never mid-`step`): a chunk
        boundary is the one point where pool + bookkeeping form a
        consistent resumable state.  ``manager`` defaults to the server's
        ``snapshot_manager``; ``step`` to the sweep clock.
        """
        from repro.serve_mc import snapshot as snap

        mgr = manager if manager is not None else self.snapshot_manager
        if mgr is None:
            raise ValueError(
                "no snapshot manager: pass one here or construct the server "
                "with snapshot_manager=..."
            )
        if isinstance(mgr, str):
            from repro.ckpt.manager import CheckpointManager

            mgr = CheckpointManager(mgr)
        step = snap.save_snapshot(self, mgr, step=step, blocking=blocking)
        self._last_snapshot_sweep = self.sweeps_elapsed
        return step

    @classmethod
    def restore(cls, source, **overrides) -> "SampleServer":
        """Rebuild a server from a snapshot (`serve_mc.snapshot.
        restore_server`) and continue bit-exactly — optionally on a
        different device mesh (``mesh=...``) or backend."""
        from repro.serve_mc import snapshot as snap

        return snap.restore_server(source, **overrides)

    # -- reporting ------------------------------------------------------------

    @staticmethod
    def _wait_summary(waits: list[float]) -> dict:
        if not waits:
            return {"count": 0}
        arr = np.sort(np.asarray(waits, np.float64))
        return {
            "count": int(arr.size),
            "mean_s": float(arr.mean()),
            "p50_s": float(np.percentile(arr, 50)),
            "p95_s": float(np.percentile(arr, 95)),
            "max_s": float(arr[-1]),
        }

    def _wait_recent_summary(self) -> dict:
        out = {"window": self._wait_recent.maxlen, "count": len(self._wait_recent)}
        if not self._wait_recent:
            return out
        secs = np.asarray([w for w, _ in self._wait_recent], np.float64)
        sweeps = np.asarray([s for _, s in self._wait_recent], np.float64)
        out.update(
            p50_s=float(np.percentile(secs, 50)),
            p95_s=float(np.percentile(secs, 95)),
            p50_sweeps=float(np.percentile(sweeps, 50)),
            p95_sweeps=float(np.percentile(sweeps, 95)),
        )
        return out

    def stats(self) -> dict:
        n = self.engine.model.num_spins
        # Utilization split: useful sweeps advanced a resident job; idle
        # resweeps advanced a free slot's stale state (wasted work, never
        # wrong work) because the whole batch launches together.
        useful = self.busy_slot_sweeps
        idle = self.total_slot_sweeps - useful
        by_user: dict[str, list] = defaultdict(list)
        by_priority: dict[int, list] = defaultdict(list)
        all_waits: list[float] = []
        for user, priority, wait_s, _wait_sweeps in self._wait_records:
            by_user[user].append(wait_s)
            by_priority[priority].append(wait_s)
            all_waits.append(wait_s)
        return {
            "slots": self.slots,
            "policy": self.policy.name,
            "launches": self.launches,
            # Distinct chunk sizes == distinct compiled run executables
            # (num_sweeps is a static jit arg); the adaptive chunker keeps
            # this bounded by its menu size.
            "distinct_chunks": len(self.launch_chunks),
            "busy_slot_sweeps": self.busy_slot_sweeps,
            "total_slot_sweeps": self.total_slot_sweeps,
            "useful_slot_sweeps": useful,
            "idle_resweep_slot_sweeps": idle,
            "sweeps_elapsed": self.sweeps_elapsed,
            "preemptions": self.preemptions,
            "utilization": (
                self.busy_slot_sweeps / self.total_slot_sweeps
                if self.total_slot_sweeps
                else 0.0
            ),
            # One attempted Metropolis update per spin per sweep.
            "spin_flips": self.busy_slot_sweeps * n,
            # Queue-wait aggregates (first-admission wall wait), overall
            # and split per user / per priority class, so the scheduling
            # bench reads its latency numbers straight off stats().
            "queue_wait": {
                "overall": self._wait_summary(all_waits),
                "by_user": {u: self._wait_summary(w) for u, w in by_user.items()},
                "by_priority": {
                    p: self._wait_summary(w) for p, w in by_priority.items()
                },
            },
            # Rolling window over the last `wait_window` admissions: the
            # recency signal (p50/p95 in wall seconds AND sweeps) that a
            # long-lived server's alerting reads — since-start aggregates
            # dilute a fresh latency regression to invisibility.
            "queue_wait_recent": self._wait_recent_summary(),
            # Placement health: how many admissions landed device-affine
            # vs spanning, how often the rebalancer had to migrate, and
            # which PT swap path ran (DESIGN.md §Scheduling/Placement).
            "placement": {
                "mode": self._pool.mode,
                "devices": self.devices,
                "free_by_device": self._pool.free_by_device(),
                "affine": self._c_place_affine.value,
                "spanning": self._c_place_span.value,
                "rebalance_migrations": self._c_migrations.value,
                "pt_swap_local": self._c_swap_local.value,
                "pt_swap_cross": self._c_swap_cross.value,
            },
            # Every number above reads the telemetry registry (the same
            # source the Prometheus/JSON exporters scrape); this block is
            # the registry's own health.
            "telemetry": {
                "enabled": self.telemetry.enabled,
                "events_recorded": self.telemetry.num_events,
                "events_dropped": self.telemetry.dropped_events,
                "straggler_events": self._c_straggler.value,
                "devices": self.devices,
            },
        }
