"""`SweepEngine` — the single construction path for Metropolis sweeps.

The paper's thesis is that explicit vectorization (CPU SSE lanes) and
explicit memory coalescing (GPU warps) are the *same* transformation over
different memory layouts.  The engine encodes that: every (rung, backend)
combination is one registration in a dispatch table, not a hand-rolled
driver.  One API owns the full sweep lifecycle:

    eng = SweepEngine.build(model, rung="a4", backend="pallas", batch=115)
    carry = eng.init_carry(seed=0)
    carry = eng.run(carry, num_sweeps)       # cached jit per num_sweeps
    spins = eng.spins_flat(carry)            # (B, N) layer-major

Carry layout (`SweepCarry`) is batched over replicas everywhere so that
parallel tempering's 115-replica production scenario is the *same* code as
a single-replica benchmark with ``batch=1``:

    spins/h_space/h_tau   (B, N) f32          for flat rungs  a1/a2
                          (B, rows, V) f32    for lane rungs  a3/a4
    betas                 (B,)  f32           per-replica inverse temperature
    rng                   (624, B) uint32     flat rungs: one scalar MT19937
                                              per replica
                          (624, B*V) uint32   lane rungs: V interlaced
                                              generators per replica
                                              (replica b owns columns
                                              b*V..(b+1)*V)

RNG placement per backend (see DESIGN.md §RNG fusion):

  * ``backend="jnp"``    — uniforms are generated on the host side of the
    sweep: one `mt19937.mt_uniform_blocks` call per sweep produces
    ceil(rows/624) blocks for all B*V lanes at once, and the first ``rows``
    rows feed the vmapped sweep.
  * ``backend="pallas"`` — the MT19937 twist/temper runs *inside* the sweep
    kernel (kernels/metropolis_kernel.py): each grid step owns its
    replica's (624, 128) state block in VMEM, regenerates its uniforms per
    sweep, and loops ``num_sweeps`` sweeps in one `pallas_call`.

Both paths evaluate the identical twist -> temper -> 24-bit-float pipeline
on the identical per-replica state columns, so jnp and Pallas(interpret)
runs are bit-exact (tested in tests/test_engine.py).

MULTI-TENANT engines (`SweepEngine.build_multi([m0, m1, ...])`) serve one
model PER SLOT in the same fused launch: coupling/field tables are
promoted from closure-captured constants to batched ``[B, ...]`` kernel
inputs (`slot_tables`), with topology (``space_nbr``) shared across slots.
Homogeneous multi == single-model engine bit for bit; see DESIGN.md
§Multi-tenancy and the slot-table APIs below.

Adding a backend (TPU non-interpret, Triton/GPU, ...) is a registration:

    register_backend("mybackend", builder)

where ``builder(engine) -> fn(carry, num_sweeps) -> carry`` closes over the
engine's precomputed model tables.  The engine wraps the returned function
in one persistent ``jax.jit`` (num_sweeps static), so repeated `run` calls
hit the compile cache — the steady-state benchmarking contract that
`metropolis.make_sweeper` used to provide.

MESH-SHARDED engines (``create(..., mesh=...)``) extend the same layout
story one level up (DESIGN.md §Mesh): the batch axis of the carry —
spins, fields, betas, RNG state columns — and the per-slot coupling
tables shard over a 1-D ``("data",)`` mesh, and `run` becomes one
`shard_map` whose per-device body is the UNMODIFIED single-device builder
at the per-device batch.  Slots are independent (separate carry rows,
separate MT19937 lane columns), so the sweep hot path has zero
cross-device traffic and sharded-vs-single-device execution is bit-exact
(tests/test_sharded.py).  Slot APIs keep addressing GLOBAL slot indices —
GSPMD resolves the (device, local slot) placement — so the serving layer
works unmodified over the enlarged pool.

HETEROGENEOUS meshes (``create(..., mesh=..., capacities=[4, 2, 1, 1])``)
drop the equal-split requirement: device d owns ``capacities[d]`` slots
and global slot ``b`` maps to its (device, local slot) through a
prefix-sum lookup instead of integer division.  Physically the carry is
laid out as PADDED ``[D, B_max]`` blocks (``B_max = max(capacities)``):
every device sweeps B_max rows so the per-device body — and therefore
every compiled kernel — is the unmodified homogeneous one, and the
``D * B_max - B`` padding rows are ordinary idle slots that no API ever
addresses (logical slot indices ``0..B-1`` translate through
`phys_slots`; `extract_pool` stores logical rows only, which is what lets
a snapshot taken under one capacity vector restore onto any other).
Equal capacity vectors have no padding — physical == logical — so they
reproduce the homogeneous path bit for bit and code path for code path.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import copy
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import ising, metropolis, mt19937 as mt, reorder
from repro.obs.telemetry import Telemetry

f32 = jnp.float32

RUNGS = ("a1", "a2", "a3", "a4", "cb")
FLAT_RUNGS = ("a1", "a2")
LANE_RUNGS = ("a3", "a4", "cb")
#: Rungs the Pallas backend implements (fully-vectorized lane layouts).
PALLAS_RUNGS = ("a4", "cb")
#: Rungs the multi-tenant (per-slot coupling tables) path implements.
MULTI_RUNGS = ("a4", "cb")

#: Default exp flavour per rung (the paper's A.1 uses exact exp; every
#: later rung uses the bit-trick fastexp).  "cb" is the graph-colored
#: sublattice rung beyond the paper's ladder: one sweep is C whole-lattice
#: vector updates instead of `rows` sequential row steps (same stationary
#: distribution, different chain — see DESIGN.md §Coloring).
DEFAULT_EXP = {"a1": "exact", "a2": "fast", "a3": "fast", "a4": "fast", "cb": "fast"}

#: Seed-scrambling multiplier for per-lane MT19937 seeds (Knuth's 2^32/phi,
#: the same constant the seed code has always used).
LANE_SEED_MULT = np.uint32(2654435761)


class SweepCarry(NamedTuple):
    """Batched sweep state: everything `run` needs, nothing it doesn't."""

    spins: jax.Array  # (B, N) | (B, rows, V)
    h_space: jax.Array  # same shape as spins
    h_tau: jax.Array  # same shape as spins
    betas: jax.Array  # (B,)
    rng: jax.Array  # (624, B) | (624, B*V) uint32


class PoolState(NamedTuple):
    """A whole slot pool's resumable state on HOST, in GLOBAL layout
    (`extract_pool`).

    The server-snapshot analogue of `ParkedSlot`: every slot row of the
    batched carry — including idle slots' stale state, whose resweeps are
    part of the pool's deterministic trajectory — plus, on multi-tenant
    engines, the full batched coupling tables.  Leaves are numpy arrays
    de-sharded ONCE (one gather per leaf, not per slot), so the state is
    mesh-independent: `splice_pool` re-shards it against whatever mesh the
    restoring engine runs on (D=4 -> D=1 and back are both just a
    `device_put`), and the resumed pool is bit-identical either way."""

    carry: SweepCarry  # numpy leaves, global batch layout
    tables: dict | None  # numpy batched coupling tables (multi only)


class ParkedSlot(NamedTuple):
    """A preempted slot's complete resumable state (`park_slot`).

    ``carry`` is the single-slot `SweepCarry` at the chunk boundary the
    slot was evicted on; ``tables`` is the slot's single-slot coupling
    tables on multi-tenant engines (None on single-model engines, where
    the couplings are engine constants).  Re-splicing both (`resume_slot`)
    continues the slot's trajectory bit-exactly: the RNG stream position
    is a pure function of sweeps completed, so an eviction gap is
    invisible to the resumed chain (DESIGN.md §Scheduling)."""

    carry: SweepCarry
    tables: dict | None


class SlotHandle:
    """All per-slot operations on one logical slot, behind one object
    (`engine.slot(b)`).

    The engine historically exposed the slot lifecycle as parallel call
    families — `extract_slot`/`splice_slot` for the carry row,
    `extract_slot_tables`/`splice_slot_tables` for the multi-tenant
    coupling row, `park_slot`/`resume_slot` stitching both — and every
    caller (scheduler preemption, snapshot restore) had to thread the
    pairs in lockstep.  A handle closes over (engine, logical index) and
    does the stitching itself: `extract()` always returns a complete
    `ParkedSlot` (tables included when the engine is multi-tenant),
    `splice()` accepts either a `ParkedSlot` or a bare single-slot
    carry.  `park`/`resume` are the same operations under the
    scheduler's names.  Handles are cheap value objects — create them
    on the fly, never cache across engines.
    """

    __slots__ = ("engine", "index")

    def __init__(self, engine: "SweepEngine", index: int):
        self.engine = engine
        self.index = index

    def __repr__(self) -> str:
        return f"SlotHandle(b={self.index}, device={self.device})"

    @property
    def device(self) -> int:
        """Mesh device owning this slot (0 when unsharded)."""
        return self.engine.slot_device(self.index)

    def extract(self, carry: SweepCarry) -> ParkedSlot:
        """This slot's complete resumable state (carry row + coupling
        row on multi-tenant engines).  Pure read."""
        eng, b = self.engine, self.index
        tables = eng.extract_slot_tables(b) if eng.multi else None
        return ParkedSlot(eng.extract_slot(carry, b), tables)

    def splice(
        self,
        carry: SweepCarry,
        state,
        model: "ising.LayeredModel | None" = None,
    ) -> SweepCarry:
        """Write ``state`` — a `ParkedSlot` or a bare single-slot
        `SweepCarry` — into this slot; returns the updated carry.

        A `ParkedSlot` with tables splices them too; ``model`` (multi-
        tenant, optional) records the tables' provenance so later
        `set_slot_model` calls for the same tenant can no-op.  A bare
        carry with ``model`` set installs that model's tables first
        (fresh-admission shape: `set_slot_model` + carry splice).
        """
        eng, b = self.engine, self.index
        if isinstance(state, ParkedSlot):
            if eng.multi and state.tables is not None:
                eng.splice_slot_tables(b, state.tables)
                if model is not None:
                    check_same_topology(eng.model, model)
                    eng.models = (
                        eng.models[:b] + (model,) + eng.models[b + 1 :]
                    )
            return eng.splice_slot(carry, b, state.carry)
        if model is not None:
            eng.set_slot_model(b, model)
        return eng.splice_slot(carry, b, state)

    def park(self, carry: SweepCarry) -> ParkedSlot:
        """`extract` under the scheduler's preemption name."""
        return self.extract(carry)

    def resume(
        self,
        carry: SweepCarry,
        parked: ParkedSlot,
        model: "ising.LayeredModel | None" = None,
    ) -> SweepCarry:
        """`splice` under the scheduler's preemption name."""
        return self.splice(carry, parked, model=model)


def lane_seeds(batch: int, V: int, seed: int) -> np.ndarray:
    """Per-lane MT19937 seeds for `batch` replicas of `V` interlaced lanes.

    Replica ``b`` owns lanes ``b*V .. (b+1)*V`` — for batch=1 this matches
    the historical `metropolis` seeding and for batch=R the historical
    `tempering` seeding, so both shim paths stay bit-exact.
    """
    return (
        np.arange(batch * V, dtype=np.uint32) * LANE_SEED_MULT + np.uint32(seed)
    )


def normalize_capacities(devices: int, batch: int, capacities=None) -> tuple[int, ...]:
    """Validate a per-device slot capacity vector (or synthesize the equal
    split when ``capacities`` is None).

    The contract shared by the engine's ragged carry layout and the
    scheduler's `SlotPool`: ``len == devices``, every entry a non-negative
    int (zero-capacity devices are legal — a host CPU in an accelerator
    mesh may contribute no slots), at least one entry positive, and the
    sum equal to the LOGICAL batch.  The equal split requires
    ``batch % devices == 0``, preserving the homogeneous-mesh validation.
    """
    if capacities is None:
        if batch % devices != 0:
            raise ValueError(
                f"batch {batch} must divide evenly over {devices} devices "
                "(pass capacities=[...] for an uneven split)"
            )
        return (batch // devices,) * devices
    caps = tuple(int(c) for c in capacities)
    if len(caps) != devices:
        raise ValueError(
            f"capacities has {len(caps)} entries for {devices} devices"
        )
    if any(c < 0 for c in caps):
        raise ValueError(f"capacities must be >= 0, got {caps}")
    if not any(caps):
        raise ValueError("at least one device needs capacity > 0")
    if sum(caps) != batch:
        raise ValueError(
            f"capacities sum {sum(caps)} != batch {batch}"
        )
    return caps


# -----------------------------------------------------------------------------
# Model-table helpers shared by the single- and multi-model construction paths.
# -----------------------------------------------------------------------------


def check_same_topology(base: ising.LayeredModel, other: ising.LayeredModel,
                        what: str = "model") -> None:
    """Multi-tenant slots share ONE lattice: same (n, L) lane shape and the
    identical ``space_nbr`` neighbour structure (couplings/fields may
    differ per slot — the neighbour tables, and for the colored rung the
    row coloring, are common engine structure)."""
    if other.n != base.n or other.L != base.L:
        raise ValueError(
            f"{what}: lane shape (n={other.n}, L={other.L}) differs from the "
            f"engine's (n={base.n}, L={base.L})"
        )
    if other.space_nbr.shape != base.space_nbr.shape or not np.array_equal(
        other.space_nbr, base.space_nbr
    ):
        raise ValueError(
            f"{what}: multi-tenant slots share one lattice topology; "
            "space_nbr differs from the engine's base model"
        )


def _coupling_tables(model: ising.LayeredModel) -> dict:
    """The PER-SLOT tables of the multi-tenant path: everything that may
    differ between models sharing a topology.  Doubled variants feed the
    sequential sweeps, undoubled ones the colored recompute and energy
    evaluation — identical expressions to the single-model `build`."""
    return dict(
        h=jnp.asarray(model.h, f32),
        base_J=jnp.asarray(model.space_J, f32),
        tau_J=jnp.asarray(model.tau_J, f32),
        base_J2=jnp.asarray(2.0 * model.space_J, f32),
        tau_J2=jnp.asarray(2.0 * model.tau_J, f32),
    )


# -----------------------------------------------------------------------------
# Backend registry.
# -----------------------------------------------------------------------------

_BACKENDS: dict[str, Callable[["SweepEngine"], Callable]] = {}
_MULTI_BACKENDS: dict[str, Callable[["SweepEngine"], Callable]] = {}


def register_backend(name: str, builder: Callable[["SweepEngine"], Callable]) -> None:
    """Register ``builder(engine) -> fn(carry, num_sweeps) -> carry``.

    The builder runs once at `SweepEngine.build` time and may close over
    `engine.tables` (precomputed jnp model arrays).  The returned function
    must be jit-traceable with ``num_sweeps`` static.
    """
    _BACKENDS[name] = builder


def register_multi_backend(
    name: str, builder: Callable[["SweepEngine"], Callable]
) -> None:
    """Register the multi-tenant flavour of a backend:
    ``builder(engine) -> fn(carry, slot_tables, num_sweeps) -> carry``.

    Unlike the single-model builder, coupling tables are NOT closed over:
    they arrive per call as a pytree of ``[B, ...]`` per-slot arrays
    (`engine.slot_tables`), so one compiled executable serves any mix of
    models sharing the engine's topology.
    """
    _MULTI_BACKENDS[name] = builder


def backends() -> tuple[str, ...]:
    return tuple(sorted(_BACKENDS))


class SweepEngine:
    """One sweep lifecycle: model tables + dispatch + cached jit."""

    def __init__(
        self,
        model: ising.LayeredModel,
        rung: str,
        backend: str,
        batch: int,
        V: int,
        exp_flavor: str,
        interpret: bool | None,
        tables: dict,
        replica_tile: int | None = None,
        models: tuple | None = None,
        slot_tables: dict | None = None,
        mesh: Mesh | None = None,
        capacities=None,
    ):
        self.model = model
        self.rung = rung
        self.backend = backend
        # Where the engine's host-side spans go: a server hands its own
        # registry over; alone, the engine records nothing.
        self.telemetry = Telemetry(enabled=False)
        self.batch = batch  # LOGICAL slot count — what every public API sees
        self.V = V
        self.exp_flavor = exp_flavor
        self.interpret = interpret
        self.tables = tables
        self.replica_tile = replica_tile
        self.rows = tables.get("rows")  # lane rungs only
        self.mesh = mesh
        if mesh is None and capacities is not None:
            raise ValueError("capacities need a mesh-sharded engine (mesh=...)")
        # Ragged-capacity layout (DESIGN.md §Mesh/Heterogeneous): on a mesh
        # with per-device capacities the carry is laid out as padded
        # [D, B_max] physical blocks; logical slot b lives at physical row
        # _phys_index[b] and its device comes from the capacity prefix
        # sums.  Equal capacities (or no mesh) make physical == logical and
        # every translation below the identity — the homogeneous bit-exact
        # path, unchanged.
        if mesh is not None:
            self.capacities = self._validate_mesh(
                mesh, batch, replica_tile, capacities
            )
            D = mesh.shape["data"]
            b_max = max(self.capacities)
            self._cum = np.concatenate(
                [[0], np.cumsum(self.capacities)]
            ).astype(np.int64)
            self._phys_index = np.concatenate(
                [
                    d * b_max + np.arange(c, dtype=np.int64)
                    for d, c in enumerate(self.capacities)
                ]
            )
            self._phys_batch = D * b_max
        else:
            self.capacities = None
            self._cum = None
            self._phys_index = np.arange(batch, dtype=np.int64)
            self._phys_batch = batch
        self._ragged = self._phys_batch != self.batch
        self._pad_state = None  # lazy deterministic padding-row template
        # Multi-tenant state (`create` with a model list): per-slot models
        # and their batched coupling tables, fed to the run jit as
        # ARGUMENTS so one executable serves any model mix sharing the
        # engine's topology.  ``models`` stays LOGICAL length; the tables
        # are physical (padding rows carry the base model's couplings).
        self.multi = models is not None
        self.models = models
        if self._ragged and slot_tables is not None:
            slot_tables = self._expand_tables(slot_tables)
        self.slot_tables = slot_tables
        if mesh is not None and slot_tables is not None:
            self.slot_tables = jax.device_put(slot_tables, self._table_shardings())
        if self.multi:
            builder = _MULTI_BACKENDS[backend]
            body = builder(self._local_view()) if mesh is not None else builder(self)
            run = self._sharded_run_multi(body) if mesh is not None else body
            self._run_jit = jax.jit(run, static_argnums=(2,))
        else:
            builder = _BACKENDS[backend]
            body = builder(self._local_view()) if mesh is not None else builder(self)
            run = self._sharded_run(body) if mesh is not None else body
            self._run_jit = jax.jit(run, static_argnums=(1,))
        self._splice_jit = None  # built lazily on first splice_slot
        self._extract_jit = None
        self._splice_tables_jit = None
        self._extract_tables_jit = None
        self._set_tau_jit = None
        self._set_betas_jit = None
        self._energies_jit = None
        # Per-model slot-table cache: admission is on the serving fast
        # path and a server's tenant set recurs, so a model's tables are
        # uploaded once, not per admit.  Models are kept strongly
        # referenced so a dead id can never alias a new model.
        self._slot_tables_cache: dict[int, tuple] = {}

    # -- construction ---------------------------------------------------------

    @classmethod
    def create(
        cls,
        models,
        rung: str = "a4",
        backend: str = "jnp",
        *,
        batch: int | None = None,
        V: int = 4,
        exp_flavor: str | None = None,
        interpret: bool | None = None,
        replica_tile: int | None = None,
        mesh: Mesh | None = None,
        capacities=None,
    ) -> "SweepEngine":
        """THE constructor: one entry point for every engine flavour.

        ``models`` is either a single `LayeredModel` (single-model engine;
        ``batch`` replica slots, default 1) or a sequence of models (one
        slot per entry, multi-tenant — per-slot coupling tables ride as
        batched kernel inputs; ``batch`` must be omitted or equal the
        list length).  ``replica_tile`` (pallas only) sizes the kernel's
        resident replica group to VMEM — must divide the per-device
        batch; None = all of it.  ``mesh`` (a 1-D ``("data",)`` mesh,
        e.g. `launch.mesh.make_slot_mesh`) shards the batch axis over its
        D devices — ``batch`` stays the GLOBAL slot count.  ``capacities``
        (mesh engines only) is the per-device slot capacity vector for a
        heterogeneous mesh: length D, summing to ``batch``; None keeps
        the equal split (which then must divide evenly).

        The deprecated `build`/`build_multi` classmethods are thin
        bit-exact shims over this path.
        """
        if isinstance(models, ising.LayeredModel):
            return cls._create_single(
                models, rung, backend,
                batch=1 if batch is None else batch,
                V=V, exp_flavor=exp_flavor, interpret=interpret,
                replica_tile=replica_tile, mesh=mesh, capacities=capacities,
            )
        models = tuple(models)
        if batch is not None and batch != len(models):
            raise ValueError(
                f"batch {batch} != len(models) {len(models)} — multi-tenant "
                "engines have exactly one slot per model"
            )
        return cls._create_multi(
            models, rung, backend, V=V, exp_flavor=exp_flavor,
            interpret=interpret, replica_tile=replica_tile, mesh=mesh,
            capacities=capacities,
        )

    @classmethod
    def build(
        cls,
        model: ising.LayeredModel,
        rung: str = "a4",
        backend: str = "jnp",
        *,
        batch: int = 1,
        V: int = 4,
        exp_flavor: str | None = None,
        interpret: bool | None = None,
        replica_tile: int | None = None,
        mesh: Mesh | None = None,
        capacities=None,
    ) -> "SweepEngine":
        """DEPRECATED — use `SweepEngine.create` (bit-exact shim)."""
        warnings.warn(
            "SweepEngine.build is deprecated; use SweepEngine.create",
            DeprecationWarning,
            stacklevel=2,
        )
        return cls._create_single(
            model, rung, backend, batch=batch, V=V, exp_flavor=exp_flavor,
            interpret=interpret, replica_tile=replica_tile, mesh=mesh,
            capacities=capacities,
        )

    @classmethod
    def _create_single(
        cls,
        model: ising.LayeredModel,
        rung: str,
        backend: str,
        *,
        batch: int,
        V: int,
        exp_flavor: str | None,
        interpret: bool | None,
        replica_tile: int | None,
        mesh: Mesh | None,
        capacities=None,
    ) -> "SweepEngine":
        if rung not in RUNGS:
            raise ValueError(f"unknown rung {rung!r}; choose from {RUNGS}")
        if backend not in _BACKENDS:
            raise ValueError(
                f"unknown backend {backend!r}; registered: {backends()}"
            )
        if batch < 1:
            raise ValueError(f"batch must be >= 1, got {batch}")
        exp_flavor = exp_flavor or DEFAULT_EXP[rung]
        tables: dict = {}
        if rung in FLAT_RUNGS:
            if rung == "a1":
                ge, J, istau, incident = ising.original_arrays(model)
                tables.update(
                    graph_edges=jnp.asarray(ge),
                    J=jnp.asarray(J),
                    is_tau=jnp.asarray(istau),
                    incident=jnp.asarray(incident),
                )
            else:
                targets, J2 = ising.flat_arrays(model)
                tables.update(targets=jnp.asarray(targets), J2=jnp.asarray(J2))
        else:
            tables.update(cls._lane_tables(model, rung, V))
        cls._validate_backend_opts(rung, backend, V, batch, replica_tile)
        return cls(
            model, rung, backend, batch, V, exp_flavor, interpret, tables,
            replica_tile, mesh=mesh, capacities=capacities,
        )

    @staticmethod
    def _lane_tables(model: ising.LayeredModel, rung: str, V: int) -> dict:
        """Shared lane-rung tables (identical in single- and multi-model
        construction; in multi mode the coupling entries are the base
        model's and serve only structure/energy defaults — per-slot
        couplings live in `slot_tables`)."""
        tables: dict = {"rows": reorder.check_lane_shape(model.n, model.L, V)}
        tables.update(
            base_nbr=jnp.asarray(model.space_nbr),
            base_J2=jnp.asarray(2.0 * model.space_J),
            tau_J2=jnp.asarray(2.0 * model.tau_J),
            # Undoubled couplings + fields, for consumers that evaluate
            # energies over the lane layout (e.g. tempering swaps).
            base_J=jnp.asarray(model.space_J),
            tau_J=jnp.asarray(model.tau_J),
            h=jnp.asarray(model.h),
        )
        if rung == "cb":
            # Host-numpy gather tables; both backends close over them
            # as trace-time constants.
            tables["classes"] = reorder.colored_classes(model, V)
        return tables

    @staticmethod
    def _validate_backend_opts(
        rung: str, backend: str, V: int, batch: int, replica_tile: int | None
    ) -> None:
        if backend == "pallas":
            if rung not in PALLAS_RUNGS:
                raise ValueError(
                    "backend='pallas' implements the fully-vectorized rungs "
                    f"{PALLAS_RUNGS} only; got rung={rung!r}"
                )
            from repro.kernels import ops  # deferred: kernels are optional

            if V != ops.LANES:
                raise ValueError(
                    f"backend='pallas' requires V={ops.LANES} (TPU lanes); got V={V}"
                )
            if replica_tile is not None and batch % replica_tile != 0:
                raise ValueError(
                    f"replica_tile {replica_tile} must divide batch {batch}"
                )
        elif replica_tile is not None:
            raise ValueError("replica_tile is a pallas-backend knob")

    # -- mesh sharding (DESIGN.md §Mesh) --------------------------------------
    #
    # A sharded engine lays the batch axis out as [D, B/D] over the mesh's
    # "data" axis.  Slots are already independent (own carry rows, own
    # MT19937 lane columns — the twist is row-wise, never cross-column), so
    # the per-device body of `run` is the existing single-device builder at
    # ``batch = B/D`` and the hot path needs no collectives: sharded
    # execution is bit-exact with the D=1 engine by construction.

    @staticmethod
    def _validate_mesh(
        mesh: Mesh, batch: int, replica_tile: int | None, capacities=None
    ) -> tuple[int, ...]:
        if "data" not in mesh.shape:
            raise ValueError(
                f'engine meshes need a "data" axis; got {dict(mesh.shape)}'
            )
        extra = {a: s for a, s in mesh.shape.items() if a != "data" and s != 1}
        if extra:
            raise ValueError(
                "engine slots shard over the \"data\" axis only; mesh has "
                f"non-trivial axes {extra}"
            )
        D = mesh.shape["data"]
        caps = normalize_capacities(D, batch, capacities)
        b_max = max(caps)
        if replica_tile is not None and b_max % replica_tile != 0:
            raise ValueError(
                f"replica_tile {replica_tile} must divide the per-device "
                f"batch {b_max}"
            )
        return caps

    def _local_view(self) -> "SweepEngine":
        """A shallow copy with the PER-DEVICE batch.  Backend builders
        close over ``eng.batch`` (uniform reshapes, kernel grids); under
        `shard_map` the body sees local shards, so it must be built for
        the per-device block — ``B/D`` rows, or ``B_max`` padded rows on a
        ragged-capacity mesh.  Everything else (model, tables, rung,
        flavor) is shared by reference — the builders treat them as
        read-only."""
        loc = copy.copy(self)
        loc.batch = self._phys_batch // self.mesh.shape["data"]
        loc.mesh = None
        return loc

    def _carry_pspecs(self) -> SweepCarry:
        """PartitionSpecs laying the carry's batch axis over "data": rows
        of spins/fields/betas shard directly; the RNG state (624, B*lanes)
        shards its COLUMN axis — slot b's lane columns land on the device
        that owns row b, because both are contiguous [D, B/D(*lanes)]
        blocks of the same slot order."""
        row = P("data", *([None] * (2 if self.rung in LANE_RUNGS else 1)))
        return SweepCarry(row, row, row, P("data"), P(None, "data"))

    def _carry_shardings(self) -> SweepCarry:
        return SweepCarry(
            *(NamedSharding(self.mesh, s) for s in self._carry_pspecs())
        )

    def _table_pspecs(self):
        return jax.tree_util.tree_map(
            lambda x: P("data", *([None] * (x.ndim - 1))), self.slot_tables
        )

    def _table_shardings(self):
        return jax.tree_util.tree_map(
            lambda x: NamedSharding(self.mesh, P("data", *([None] * (x.ndim - 1)))),
            self.slot_tables,
        )

    def _sharded_run(self, body: Callable) -> Callable:
        """Wrap a per-device run body in `shard_map`: one call advances all
        D*B/D slots with zero cross-device traffic (the body is closed over
        ``num_sweeps`` so the static argument never crosses the shard_map
        boundary; each chunk size still compiles once)."""
        specs, mesh = self._carry_pspecs(), self.mesh

        def run(carry: SweepCarry, num_sweeps: int) -> SweepCarry:
            f = jax.shard_map(
                lambda c: body(c, num_sweeps), mesh=mesh,
                in_specs=(specs,), out_specs=specs, check_vma=False,
            )
            return f(carry)

        return run

    def _sharded_run_multi(self, body: Callable) -> Callable:
        specs, tab_specs, mesh = (
            self._carry_pspecs(), self._table_pspecs(), self.mesh,
        )

        def run(carry: SweepCarry, tabs: dict, num_sweeps: int) -> SweepCarry:
            f = jax.shard_map(
                lambda c, tb: body(c, tb, num_sweeps), mesh=mesh,
                in_specs=(specs, tab_specs), out_specs=specs, check_vma=False,
            )
            return f(carry, tabs)

        return run

    def device_ready_times(self, carry: SweepCarry, t0: float) -> np.ndarray:
        """(D,) wall seconds from ``t0`` until each device's shard of the
        carry was ready, in mesh device order (sharded engines only).

        The observability layer's straggler probe (DESIGN.md
        §Observability): after a `run` launch, one waiter thread per
        device blocks on that device's addressable spins shard and
        timestamps when it became ready — so each device's completion is
        measured independently and a straggling device shows up as the
        one whose ready time dominates the launch, wherever it sits in
        device order (`block_until_ready` waits in the runtime with the
        GIL released, so the waiters don't serialize each other).  Pure
        reads; the carry is untouched (`obs.LaunchSkewMonitor` consumes
        the series).
        """
        if self.mesh is None:
            raise ValueError("device_ready_times needs a mesh-sharded engine")
        import threading
        import time as _time

        shards = sorted(
            carry.spins.addressable_shards, key=lambda s: s.device.id
        )
        out = np.empty(len(shards), np.float64)

        def _wait(i: int, data) -> None:
            jax.block_until_ready(data)
            out[i] = _time.perf_counter() - t0

        threads = [
            threading.Thread(target=_wait, args=(i, s.data))
            for i, s in enumerate(shards)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        return out

    def slot_energies(self, carry: SweepCarry) -> jax.Array:
        """Per-slot energies (B,) of the carry's spins, computed
        device-locally (lane rungs only).

        The cross-device tempering path (`tempering.swap_phase_from_energies`)
        gathers ONLY these B scalars: each device evaluates
        `tempering.lane_energy` over its own slot rows — its own coupling
        rows on a multi-tenant engine — so a PT ladder spanning devices
        exchanges O(R) floats per swap phase, never spins.  Unsharded
        engines take the plain vmap path; both are the same expression
        `swap_phase` evaluates internally, hence bit-identical to it.
        """
        if self.rung not in LANE_RUNGS:
            raise ValueError(
                f"slot_energies is defined for lane rungs {LANE_RUNGS}; "
                f"got rung={self.rung!r}"
            )
        if self._energies_jit is None:
            from repro.core import tempering  # deferred: tempering imports us

            t, n = self.tables, self.model.n
            nbr = t["base_nbr"]

            if self.multi:
                def local(spins, tabs):
                    return jax.vmap(
                        lambda sp, h, bJ, tJ: tempering.lane_energy(
                            sp, h, nbr, bJ, tJ, n
                        )
                    )(spins, tabs["h"], tabs["base_J"], tabs["tau_J"])
            else:
                h, bJ, tJ = t["h"], t["base_J"], t["tau_J"]

                def local(spins):
                    return jax.vmap(
                        lambda sp: tempering.lane_energy(sp, h, nbr, bJ, tJ, n)
                    )(spins)

            fn = local
            if self.mesh is not None:
                sp_spec = self._carry_pspecs().spins
                in_specs = (
                    (sp_spec, self._table_pspecs()) if self.multi else (sp_spec,)
                )
                fn = jax.shard_map(
                    local, mesh=self.mesh, in_specs=in_specs,
                    out_specs=P("data"), check_vma=False,
                )
            self._energies_jit = jax.jit(fn)
        if self.multi:
            e = self._energies_jit(carry.spins, self.slot_tables)
        else:
            e = self._energies_jit(carry.spins)
        if self._ragged:
            # Logical (B,) view: drop the padding rows so callers index by
            # logical slot.  A gather of B scalars, off the sweep hot path
            # (only swap phases read energies).
            e = e[jnp.asarray(self._phys_index)]
        return e

    @classmethod
    def build_multi(
        cls,
        models,
        rung: str = "a4",
        backend: str = "jnp",
        *,
        V: int = 4,
        exp_flavor: str | None = None,
        interpret: bool | None = None,
        replica_tile: int | None = None,
        mesh: Mesh | None = None,
        capacities=None,
    ) -> "SweepEngine":
        """DEPRECATED — use `SweepEngine.create` (bit-exact shim)."""
        warnings.warn(
            "SweepEngine.build_multi is deprecated; use SweepEngine.create",
            DeprecationWarning,
            stacklevel=2,
        )
        return cls._create_multi(
            tuple(models), rung, backend, V=V, exp_flavor=exp_flavor,
            interpret=interpret, replica_tile=replica_tile, mesh=mesh,
            capacities=capacities,
        )

    @classmethod
    def _create_multi(
        cls,
        models: tuple,
        rung: str,
        backend: str,
        *,
        V: int,
        exp_flavor: str | None,
        interpret: bool | None,
        replica_tile: int | None,
        mesh: Mesh | None,
        capacities=None,
    ) -> "SweepEngine":
        """A MULTI-TENANT engine: one slot per entry of ``models``, each
        slot sweeping its own model's couplings/fields in the same fused
        launch (the "many independent lattices per kernel" strategy of
        Weigel & Yavors'kii applied to heterogeneous instances).

        All models must share one lattice: same ``(n, L)`` lane shape and
        identical ``space_nbr`` (`check_same_topology`) — neighbour
        structure and, for the colored rung, the row coloring are common
        engine structure, while ``h``/``space_J``/``tau_J`` ride per slot
        as batched kernel inputs (`slot_tables`).  With B copies of one
        model this path is bit-identical to the single-model engine
        (tests/test_multi_tenant.py), which is what lets the serving layer
        switch to it unconditionally.
        """
        if not models:
            raise ValueError("build_multi needs at least one model")
        base = models[0]
        for i, mm in enumerate(models[1:], 1):
            check_same_topology(base, mm, what=f"models[{i}]")
        if rung not in MULTI_RUNGS:
            raise ValueError(
                f"multi-tenant engines implement rungs {MULTI_RUNGS}; "
                f"got rung={rung!r}"
            )
        if backend not in _MULTI_BACKENDS:
            raise ValueError(
                f"no multi-tenant flavour registered for backend {backend!r}; "
                f"registered: {tuple(sorted(_MULTI_BACKENDS))}"
            )
        batch = len(models)
        exp_flavor = exp_flavor or DEFAULT_EXP[rung]
        tables = cls._lane_tables(base, rung, V)
        cls._validate_backend_opts(rung, backend, V, batch, replica_tile)
        slot_tables = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[_coupling_tables(mm) for mm in models]
        )
        return cls(
            base, rung, backend, batch, V, exp_flavor, interpret, tables,
            replica_tile, models=models, slot_tables=slot_tables, mesh=mesh,
            capacities=capacities,
        )

    # -- lifecycle ------------------------------------------------------------

    def init_carry(
        self,
        seed: int = 0,
        spins: np.ndarray | None = None,
        betas: np.ndarray | None = None,
    ) -> SweepCarry:
        """Initial batched carry.

        ``spins`` may be None (per-replica random init from ``seed``), one
        flat (N,) configuration (replicated), or a (B, N) stack.  ``betas``
        defaults to the model beta on every replica (each slot's OWN
        model's beta on a multi-tenant engine); effective fields are
        likewise computed from each slot's own model.
        """
        m, B = self.model, self.batch
        # Slots whose tables were raw-spliced (model None) fall back to the
        # base model for spin/field/beta init.
        slot_models = (
            tuple(mm if mm is not None else m for mm in self.models)
            if self.multi
            else (m,) * B
        )
        if spins is None:
            spin_list = [
                ising.init_spins(mm, seed=seed * 1000 + b)
                for b, mm in enumerate(slot_models)
            ]
        else:
            spins = np.asarray(spins, np.float32)
            if spins.ndim == 1:
                spin_list = [spins] * B
            else:
                if spins.shape[0] != B:
                    raise ValueError(f"spins batch {spins.shape[0]} != {B}")
                spin_list = list(spins)
        if betas is None:
            betas = np.asarray([mm.beta for mm in slot_models], np.float32)
        betas = jnp.asarray(betas, f32)

        if self.rung in FLAT_RUNGS:
            states = [
                metropolis.make_flat_state(mm, sp)
                for mm, sp in zip(slot_models, spin_list)
            ]
            # One scalar generator per replica, seeds scrambled exactly like
            # the lane path (consecutive seeds would give nearby-seeded runs
            # bit-identical streams); batch=1 reduces to mt_init(seed), the
            # historical scalar seeding.
            rng = mt.mt_init(lane_seeds(B, 1, seed))
        else:
            states = [
                metropolis.make_lane_state(mm, sp, self.V)
                for mm, sp in zip(slot_models, spin_list)
            ]
            rng = mt.mt_init(lane_seeds(B, self.V, seed))
        stacked = [jnp.stack([s[i] for s in states]) for i in range(3)]
        carry = SweepCarry(*stacked, betas=betas, rng=rng)
        if self._ragged:
            carry = SweepCarry(
                *(jnp.asarray(x) for x in self._expand_carry(carry))
            )
        if self.mesh is not None:
            carry = jax.device_put(carry, self._carry_shardings())
        return carry

    def run(self, carry: SweepCarry, num_sweeps: int) -> SweepCarry:
        """Advance every replica by ``num_sweeps`` Metropolis sweeps.

        ``num_sweeps`` is a static jit argument: each distinct chunk size
        compiles once and then hits the persistent compile cache.  The
        serve scheduler (`repro.serve_mc`) relies on this — it runs the
        resident batch in fixed-size chunks (with occasional shorter
        remainder chunks at schedule boundaries), so steady-state serving
        is one cached fused launch per chunk.

        On a multi-tenant engine the current per-slot coupling tables ride
        along as jit ARGUMENTS (same shapes always, so still one cached
        executable per chunk size, whatever models occupy the slots).
        """
        if self.multi:
            return self._run_jit(carry, self.slot_tables, int(num_sweeps))
        return self._run_jit(carry, int(num_sweeps))

    def run_fn(self, num_sweeps: int) -> Callable[[SweepCarry], SweepCarry]:
        """Steady-state callable for benchmarking: ``fn(carry) -> carry``.

        Bound to the engine's persistent jit, so repeated timing calls hit
        the compile cache.
        """
        n = int(num_sweeps)
        if self.multi:
            return lambda carry: self._run_jit(carry, self.slot_tables, n)
        return lambda carry: self._run_jit(carry, n)

    # -- views ----------------------------------------------------------------

    def spins_flat(self, carry: SweepCarry) -> np.ndarray:
        """(B, N) spins in flat layer-major order, comparable across rungs.

        Always LOGICAL rows: on a ragged engine the padding rows of a
        full-pool carry are dropped, so consumers (observable streams,
        result finalization) index by logical slot on every layout.
        Single-slot carries (from `extract_slot`) pass through unchanged.
        """
        m = self.model
        spins = np.asarray(carry.spins)
        if self._ragged and spins.shape[0] == self._phys_batch:
            spins = spins[self._phys_index]
        if self.rung in FLAT_RUNGS:
            return spins
        return np.stack(
            [reorder.from_lane(s, m.n, m.L, self.V) for s in spins]
        )

    def state_of(self, carry: SweepCarry, b: int = 0):
        """Replica ``b`` as the historical per-replica NamedTuple."""
        cls = metropolis.FlatState if self.rung in FLAT_RUNGS else metropolis.LaneState
        pb = self._slot_phys(b)
        return cls(carry.spins[pb], carry.h_space[pb], carry.h_tau[pb])

    # -- per-slot splice/extract (the serve scheduler's admit/retire API) ------
    #
    # A batched carry is a row of independent "slots": slot b owns row b of
    # spins/h_space/h_tau/betas and its own RNG lane columns (column b for
    # flat rungs, columns b*V..(b+1)*V for lane rungs).  Because every slot
    # advances its own MT19937 lanes by the same number of blocks per sweep
    # regardless of the batch size, a slot's trajectory is a pure function
    # of its spliced-in state and the sweep count — NOT of its neighbours.
    # That is the invariant continuous batching rests on: jobs can be
    # admitted into freed slots mid-flight and still reproduce, bit for
    # bit, the run they would have had alone (tests/test_serve_mc.py).

    def _slot_lanes(self) -> int:
        """RNG lane columns owned by one slot."""
        return self.V if self.rung in LANE_RUNGS else 1

    def slot_device(self, b: int) -> int:
        """Device owning logical slot ``b`` (0 when unsharded).

        The mesh shards the batch axis as contiguous per-device blocks
        (`_carry_pspecs`), so ownership is a pure function of the index —
        the fact the scheduler's placement-aware admission builds on: a
        job whose slots share a device keeps its collective phases (PT
        swaps) on-device instead of paying a cross-device gather.  Under
        per-device capacities the lookup is the prefix-sum search over
        the capacity vector (skipping zero-capacity devices); with equal
        capacities it reduces to the historical integer division.
        """
        if self.mesh is None:
            return 0
        return int(np.searchsorted(self._cum, int(b), side="right")) - 1

    def phys_slots(self, slots) -> np.ndarray:
        """Physical carry rows of the given LOGICAL slot indices.

        The identity unless the engine is ragged (uneven capacities pad
        the carry to [D, B_max] blocks).  Callers indexing the batched
        carry directly — the PT swap path gathering its ladder's rows,
        result finalization reading betas — translate through this; the
        slot APIs translate internally.
        """
        return self._phys_index[np.asarray(slots, np.int64)]

    def _slot_phys(self, b: int) -> int:
        """Physical carry row of logical slot ``b``."""
        return int(self._phys_index[int(b)])

    def init_slot_carry(
        self,
        seed: int = 0,
        spins: np.ndarray | None = None,
        beta: float | None = None,
        rng_seeds: np.ndarray | None = None,
        model: ising.LayeredModel | None = None,
    ) -> SweepCarry:
        """A single-slot (batch=1 shaped) carry for `splice_slot`.

        Bit-identical to ``init_carry(seed=seed)`` on a ``batch=1`` engine:
        same spin init (``ising.init_spins(m, seed*1000)``), same scrambled
        per-lane RNG seeding (``lane_seeds(1, V, seed)``).  ``rng_seeds``
        overrides the per-lane seeds for callers that need a specific
        column block of a larger seeding plan (e.g. a tempering job whose
        replica b must reproduce ``lane_seeds(R, V, seed)[b*V:(b+1)*V]``).
        ``model`` (multi-tenant engines only) computes the slot's effective
        fields and default beta from a job-private model — splice its
        coupling tables into the same slot (`set_slot_model`) or the carry
        will be inconsistent with what the slot sweeps.
        """
        if model is None:
            m = self.model
        else:
            if not self.multi:
                raise ValueError(
                    "per-slot models need a multi-tenant engine (build_multi)"
                )
            check_same_topology(self.model, model)
            m = model
        if spins is None:
            spins = ising.init_spins(m, seed=seed * 1000)
        else:
            spins = np.asarray(spins, np.float32)
            if spins.ndim != 1:
                raise ValueError(f"slot spins must be flat (N,), got {spins.shape}")
        beta_arr = jnp.full((1,), m.beta if beta is None else beta, f32)
        lanes = self._slot_lanes()
        if rng_seeds is None:
            rng_seeds = lane_seeds(1, lanes, seed)
        else:
            rng_seeds = np.asarray(rng_seeds, np.uint32)
            if rng_seeds.shape != (lanes,):
                raise ValueError(
                    f"rng_seeds must have shape ({lanes},), got {rng_seeds.shape}"
                )
        if self.rung in FLAT_RUNGS:
            st = metropolis.make_flat_state(m, spins)
        else:
            st = metropolis.make_lane_state(m, spins, self.V)
        rng = mt.mt_init(rng_seeds)
        return SweepCarry(
            st.spins[None], st.h_space[None], st.h_tau[None], beta_arr, rng
        )

    def splice_slot(
        self, carry: SweepCarry, b: int, slot: SweepCarry
    ) -> SweepCarry:
        """Write a single-slot carry into slot ``b`` of a batched carry.

        One jitted call (slot index traced, so every slot shares the same
        executable): admission is on the serving fast path, and five
        separately-dispatched scatters were the dominant admit cost.
        Pure data movement — bit-exact by construction.
        """
        if not 0 <= b < self.batch:
            raise ValueError(f"slot {b} out of range for batch {self.batch}")
        if self._splice_jit is None:
            lanes = self._slot_lanes()

            def _splice(carry, b, slot):
                upd = lambda dst, src, start, axis: lax.dynamic_update_slice_in_dim(
                    dst, src, start, axis=axis
                )
                return SweepCarry(
                    upd(carry.spins, slot.spins, b, 0),
                    upd(carry.h_space, slot.h_space, b, 0),
                    upd(carry.h_tau, slot.h_tau, b, 0),
                    upd(carry.betas, slot.betas, b, 0),
                    upd(carry.rng, slot.rng, b * lanes, 1),
                )

            # On a sharded engine the updated carry must STAY sharded:
            # without pinned out_shardings GSPMD may materialise the
            # scatter's result replicated, silently de-sharding the pool.
            kw = (
                {"out_shardings": self._carry_shardings()}
                if self.mesh is not None
                else {}
            )
            self._splice_jit = jax.jit(_splice, **kw)
        return self._splice_jit(carry, jnp.int32(self._slot_phys(b)), slot)

    def extract_slot(self, carry: SweepCarry, b: int) -> SweepCarry:
        """Slot ``b`` of a batched carry as a single-slot carry (the exact
        inverse of `splice_slot`; round-trips bit-exactly)."""
        if not 0 <= b < self.batch:
            raise ValueError(f"slot {b} out of range for batch {self.batch}")
        if self._extract_jit is None:
            lanes = self._slot_lanes()

            def _extract(carry, b):
                cut = lambda src, start, size, axis: lax.dynamic_slice_in_dim(
                    src, start, size, axis=axis
                )
                return SweepCarry(
                    cut(carry.spins, b, 1, 0),
                    cut(carry.h_space, b, 1, 0),
                    cut(carry.h_tau, b, 1, 0),
                    cut(carry.betas, b, 1, 0),
                    cut(carry.rng, b * lanes, lanes, 1),
                )

            self._extract_jit = jax.jit(_extract)
        return self._extract_jit(carry, jnp.int32(self._slot_phys(b)))

    def slot(self, b: int) -> SlotHandle:
        """Handle bundling every per-slot operation on logical slot ``b``
        (`SlotHandle`): ``extract()/splice()/park()/resume()`` plus the
        owning ``device``.  The consolidated per-slot API — the legacy
        call families (`park_slot`, `resume_slot`, ...) delegate here."""
        if not 0 <= b < self.batch:
            raise ValueError(f"slot {b} out of range for batch {self.batch}")
        return SlotHandle(self, b)

    def park_slot(self, carry: SweepCarry, b: int) -> ParkedSlot:
        """Checkpoint slot ``b`` for preemption: its carry row (and, on a
        multi-tenant engine, its coupling-table row) as a `ParkedSlot`.

        Pure extraction — the slot itself is untouched and keeps
        idle-resweeping its stale state until the next admission
        overwrites it.  Delegates to ``self.slot(b).park(...)``.
        """
        return self.slot(b).park(carry)

    def resume_slot(
        self,
        carry: SweepCarry,
        b: int,
        parked: ParkedSlot,
        model: ising.LayeredModel | None = None,
    ) -> SweepCarry:
        """Re-splice a `ParkedSlot` into slot ``b`` (any slot — resumption
        need not reuse the slot the job was evicted from; slot state is
        position-independent).  The exact inverse of `park_slot`, so a
        preempted-and-resumed chain is bit-identical to an uninterrupted
        one.  ``model`` (multi-tenant, optional) records the resumed
        tables' provenance so later `set_slot_model` calls for the same
        tenant can no-op.  Delegates to ``self.slot(b).resume(...)``."""
        return self.slot(b).resume(carry, parked, model=model)

    # -- ragged padding (uneven capacities only) -------------------------------
    #
    # A ragged engine's physical carry has D*B_max rows; the padding rows
    # are ordinary idle slots no API ever addresses.  Their content is a
    # fixed deterministic template — a pure function of the base model —
    # so expanding a logical-layout pool is reproducible on any engine
    # with the same model, whatever the capacity vector.  Nothing ever
    # reads a padding row (slots are independent: own carry row, own RNG
    # columns), so padding is bit-invisible to every logical slot.

    def _pad_template(self) -> tuple:
        """(spins, h_space, h_tau, beta, rng_cols) of ONE padding slot."""
        if self._pad_state is None:
            m = self.model
            sp = ising.init_spins(m, seed=0)
            if self.rung in FLAT_RUNGS:
                st = metropolis.make_flat_state(m, sp)
            else:
                st = metropolis.make_lane_state(m, sp, self.V)
            rng = np.asarray(
                mt.mt_init(lane_seeds(1, self._slot_lanes(), 0))
            )
            self._pad_state = (
                np.asarray(st.spins),
                np.asarray(st.h_space),
                np.asarray(st.h_tau),
                np.float32(m.beta),
                rng,
            )
        return self._pad_state

    def _expand_carry(self, carry: SweepCarry) -> SweepCarry:
        """LOGICAL-layout carry -> padded physical layout (host numpy)."""
        lanes = self._slot_lanes()
        P = self._phys_batch
        p_sp, p_hs, p_ht, p_beta, p_rng = self._pad_template()

        def rows(x, fill):
            x = np.asarray(x)
            out = np.empty((P,) + x.shape[1:], x.dtype)
            out[:] = fill
            out[self._phys_index] = x
            return out

        cols = (
            self._phys_index[:, None] * lanes + np.arange(lanes)
        ).ravel()
        rng = np.tile(p_rng, (1, P))
        rng[:, cols] = np.asarray(carry.rng)
        return SweepCarry(
            rows(carry.spins, p_sp),
            rows(carry.h_space, p_hs),
            rows(carry.h_tau, p_ht),
            rows(carry.betas, p_beta),
            rng,
        )

    def _collapse_carry(self, carry: SweepCarry) -> SweepCarry:
        """Padded physical host carry -> LOGICAL layout (drops padding)."""
        lanes = self._slot_lanes()
        cols = (
            self._phys_index[:, None] * lanes + np.arange(lanes)
        ).ravel()
        return SweepCarry(
            np.asarray(carry.spins)[self._phys_index],
            np.asarray(carry.h_space)[self._phys_index],
            np.asarray(carry.h_tau)[self._phys_index],
            np.asarray(carry.betas)[self._phys_index],
            np.asarray(carry.rng)[:, cols],
        )

    def _expand_tables(self, tables: dict) -> dict:
        """LOGICAL [B, ...] slot tables -> padded physical [P, ...] (host
        numpy leaves); padding rows carry the base model's couplings."""
        fill = _coupling_tables(self.model)
        out = {}
        for k, v in tables.items():
            v = np.asarray(v)
            big = np.empty((self._phys_batch,) + v.shape[1:], v.dtype)
            big[:] = np.asarray(fill[k])
            big[self._phys_index] = v
            out[k] = jnp.asarray(big)
        return out

    def extract_pool(self, carry: SweepCarry) -> PoolState:
        """The WHOLE pool's resumable state on host, in LOGICAL global
        layout.

        One `np.asarray` per carry/table leaf — on a sharded engine that
        is one cross-device gather per leaf, not a per-slot extract loop —
        so server snapshots cost O(leaves), independent of slot count.
        On a ragged engine the padding rows are dropped, which makes the
        pool state capacity-independent: a snapshot taken under
        capacities [4, 2, 1, 1] splices onto [2, 2, 2, 2] or a D=1 engine
        unchanged.  Pure read; the carry and tables are untouched.
        """
        host = SweepCarry(*(np.asarray(x) for x in carry))
        if self._ragged:
            host = self._collapse_carry(host)
        tables = (
            {k: np.asarray(v) for k, v in self.slot_tables.items()}
            if self.multi
            else None
        )
        if self._ragged and tables is not None:
            tables = {k: v[self._phys_index] for k, v in tables.items()}
        return PoolState(host, tables)

    def splice_pool(self, pool: PoolState) -> SweepCarry:
        """Install a `PoolState` as this engine's current pool (the exact
        inverse of `extract_pool`; round-trips bit-exactly).

        The pool is in LOGICAL global layout, so THIS engine's mesh —
        which may have a different device count OR capacity vector than
        the extracting engine's — re-lays it out for its own pool: a
        ragged engine scatters the logical rows into its padded blocks
        (`_expand_carry`), then a plain `device_put` against its own
        shardings.  On multi-tenant engines the batched coupling tables
        are installed too; slot model provenance resets to None
        (raw-splice semantics: a later `set_slot_model` re-records it).
        Returns the new carry (the caller threads it through `run`, as
        always).
        """
        lanes = self._slot_lanes()
        spins = np.asarray(pool.carry.spins)
        want = (
            (self.batch, self.rows, self.V)
            if self.rung in LANE_RUNGS
            else (self.batch, self.model.num_spins)
        )
        if tuple(spins.shape) != want:
            raise ValueError(
                f"pool spins shape {spins.shape} does not fit this engine "
                f"(want {want}: batch={self.batch}, rung={self.rung!r})"
            )
        rng = np.asarray(pool.carry.rng)
        if rng.shape[1] != self.batch * lanes:
            raise ValueError(
                f"pool rng has {rng.shape[1]} lane columns; this engine "
                f"needs {self.batch * lanes}"
            )
        host = pool.carry
        if self._ragged:
            host = self._expand_carry(host)
        carry = SweepCarry(*(jnp.asarray(x) for x in host))
        if self.mesh is not None:
            carry = jax.device_put(carry, self._carry_shardings())
        if self.multi:
            if pool.tables is None:
                raise ValueError(
                    "multi-tenant engines need the pool's coupling tables"
                )
            if self._ragged:
                tabs = self._expand_tables(pool.tables)
            else:
                tabs = {k: jnp.asarray(v) for k, v in pool.tables.items()}
            self.slot_tables = tabs
            if self.mesh is not None:
                self.slot_tables = jax.device_put(
                    tabs, self._table_shardings()
                )
            self.models = (None,) * self.batch
        elif pool.tables is not None:
            raise ValueError(
                "pool carries coupling tables but this engine is single-model"
            )
        return carry

    def set_slot_betas(self, carry: SweepCarry, slots, betas) -> SweepCarry:
        """Rewrite the betas of the given slots (anneal-schedule advance,
        tempering swaps) without touching spins, fields, or RNG: beta
        ``betas[k]`` becomes slot ``slots[k]``'s, in ONE jitted call.

        Slot indices are traced and padded to the physical batch with an
        out-of-range index whose writes are dropped, so one executable
        serves every call, whatever the number of slots.  On a mesh the
        betas row stays sharded over ``data``.
        """
        slots = self._check_slots(slots)
        vals = np.asarray(betas, np.float32)
        if vals.shape != (len(slots),):
            raise ValueError(
                f"betas must have shape ({len(slots)},), got {vals.shape}"
            )
        if not slots:
            return carry
        tel = self.telemetry
        with tel.span("engine.set_betas", cat="engine"):
            tel.counter("engine.beta_rewrites").add(1)
            idx, pad = self._padded_rows(slots, vals)
            if self._set_betas_jit is None:
                self._set_betas_jit = self._build_set_betas()
            return carry._replace(
                betas=self._set_betas_jit(carry.betas, idx, pad)
            )

    def _build_set_betas(self) -> Callable:
        kw = (
            {"out_shardings": NamedSharding(self.mesh, P("data"))}
            if self.mesh is not None
            else {}
        )
        return jax.jit(
            lambda row, idx, vals: row.at[idx].set(vals, mode="drop"), **kw
        )

    def _check_slots(self, slots) -> list[int]:
        """``slots`` as ints, or ValueError unless distinct and in range."""
        slots = [int(b) for b in slots]
        if len(set(slots)) != len(slots) or not all(
            0 <= b < self.batch for b in slots
        ):
            raise ValueError(
                f"slots must be distinct and in range for batch {self.batch}: "
                f"{slots}"
            )
        return slots

    def _padded_rows(self, slots: list[int], rows: np.ndarray):
        """Physical row indices of ``slots`` and their ``rows``, padded to
        the physical batch: padding indices point past the batch (their
        writes are dropped) and padding rows are zero."""
        P_ = self._phys_batch
        idx = np.full((P_,), P_, np.int32)
        idx[: len(slots)] = self.phys_slots(slots)
        pad = np.zeros((P_,) + rows.shape[1:], np.float32)
        pad[: len(slots)] = rows
        return idx, pad

    # -- per-slot model tables (the multi-tenant admit API) --------------------
    #
    # On a multi-tenant engine every slot additionally owns a row of the
    # batched coupling tables (`slot_tables`).  These mirror the slot-carry
    # splice/extract APIs: one jitted dynamic-slice call each, slot index
    # traced so all slots share one executable.  Unlike the carry (which
    # the scheduler threads through `run`), the tables live ON the engine —
    # `run` reads `self.slot_tables` — so the splice-side APIs mutate
    # engine state and admission is simply `set_slot_model(b, job_model)`.

    def check_model(self, model: ising.LayeredModel) -> None:
        """Raise unless ``model`` is admissible in this engine's slots."""
        check_same_topology(self.model, model)

    #: Bound on the per-model table cache; a tenant set larger than this
    #: simply re-uploads (correctness is unaffected, only admit latency).
    SLOT_TABLES_CACHE_MAX = 64

    def slot_tables_for(self, model: ising.LayeredModel) -> dict:
        """Single-slot (leading dim 1) coupling tables for `splice_slot_tables`.

        Cached per model object: repeated admissions of the same tenant
        (the steady state of a multi-tenant server) skip the host-to-device
        table upload entirely.
        """
        hit = self._slot_tables_cache.get(id(model))
        if hit is not None and hit[0] is model:
            return hit[1]
        self.check_model(model)
        tabs = jax.tree_util.tree_map(lambda x: x[None], _coupling_tables(model))
        if len(self._slot_tables_cache) >= self.SLOT_TABLES_CACHE_MAX:
            self._slot_tables_cache.clear()
        self._slot_tables_cache[id(model)] = (model, tabs)
        return tabs

    def splice_slot_tables(self, b: int, slot: dict) -> None:
        """Write single-slot coupling tables into slot ``b`` (multi only).

        Pure data movement over every table leaf — bit-exact by
        construction, like `splice_slot`.  The slot's recorded model
        (`model_of`) becomes None (unknown provenance): a raw table
        splice carries no model object, and leaving a stale entry would
        let a later `set_slot_model` wrongly no-op on its identity check.
        Callers that know the model should use `set_slot_model`, which
        records it.
        """
        if not self.multi:
            raise ValueError("splice_slot_tables needs a multi-tenant engine")
        if not 0 <= b < self.batch:
            raise ValueError(f"slot {b} out of range for batch {self.batch}")
        self.models = self.models[:b] + (None,) + self.models[b + 1 :]
        if self._splice_tables_jit is None:

            def _splice(tabs, b, slot):
                return jax.tree_util.tree_map(
                    lambda dst, src: lax.dynamic_update_slice_in_dim(
                        dst, src, b, axis=0
                    ),
                    tabs,
                    slot,
                )

            kw = (
                {"out_shardings": self._table_shardings()}
                if self.mesh is not None
                else {}
            )
            self._splice_tables_jit = jax.jit(_splice, **kw)
        self.slot_tables = self._splice_tables_jit(
            self.slot_tables, jnp.int32(self._slot_phys(b)), slot
        )

    def extract_slot_tables(self, b: int) -> dict:
        """Slot ``b``'s coupling tables as a single-slot pytree (the exact
        inverse of `splice_slot_tables`; round-trips bit-exactly)."""
        if not self.multi:
            raise ValueError("extract_slot_tables needs a multi-tenant engine")
        if not 0 <= b < self.batch:
            raise ValueError(f"slot {b} out of range for batch {self.batch}")
        if self._extract_tables_jit is None:

            def _extract(tabs, b):
                return jax.tree_util.tree_map(
                    lambda src: lax.dynamic_slice_in_dim(src, b, 1, axis=0), tabs
                )

            self._extract_tables_jit = jax.jit(_extract)
        return self._extract_tables_jit(
            self.slot_tables, jnp.int32(self._slot_phys(b))
        )

    def set_slot_model(self, b: int, model: ising.LayeredModel) -> None:
        """Admit ``model`` into slot ``b``: splice its coupling tables and
        record it as the slot's model (`model_of`).

        A no-op when the slot already holds ``model`` (``models[b]``
        records exactly what was last spliced), so admissions on the
        common same-tenant path — every admission of a model-less job —
        skip the table splice entirely.
        """
        if not self.multi:
            raise ValueError("set_slot_model needs a multi-tenant engine")
        if not 0 <= b < self.batch:
            raise ValueError(f"slot {b} out of range for batch {self.batch}")
        if self.models[b] is model:
            return
        self.splice_slot_tables(b, self.slot_tables_for(model))
        self.models = self.models[:b] + (model,) + self.models[b + 1 :]

    def model_of(self, b: int) -> ising.LayeredModel | None:
        """The model slot ``b`` currently sweeps (None if its tables were
        last written by a raw `splice_slot_tables` or `set_slot_tau`)."""
        return self.models[b] if self.multi else self.model

    def set_slot_tau(self, carry: SweepCarry, slots, tau_rows) -> SweepCarry:
        """Rewrite the tau couplings of many slots in ONE jitted call
        (multi only): row ``k`` of ``tau_rows`` (``(len(slots), n)``)
        becomes slot ``slots[k]``'s ``tau_J``, and twice it its
        ``tau_J2``.  Returns the carry.

        Slot indices are traced and padded to the physical batch with an
        out-of-range index whose writes are dropped, so one executable
        serves every call, whatever the number of slots.  The a4 kernels
        carry incremental fields, so the same call rebuilds those slots'
        ``h_tau`` from their spins (`metropolis.lane_h_tau`); the cb
        kernels recompute fields from the tables on entry and leave the
        carry alone.  The slots' recorded models become None, as after a
        raw `splice_slot_tables`: their tables match no model object.
        """
        if not self.multi:
            raise ValueError("set_slot_tau needs a multi-tenant engine")
        slots = self._check_slots(slots)
        rows = np.asarray(tau_rows, np.float32)
        n = self.model.n
        if rows.shape != (len(slots), n):
            raise ValueError(
                f"tau_rows must have shape ({len(slots)}, {n}), got {rows.shape}"
            )
        if not slots:
            return carry
        tel = self.telemetry
        with tel.span("engine.set_tau", cat="engine"):
            tel.counter("engine.table_rewrites").add(1)
            idx, pad = self._padded_rows(slots, rows)
            if self._set_tau_jit is None:
                self._set_tau_jit = self._build_set_tau()
            if self.rung == "a4":
                self.slot_tables, carry = self._set_tau_jit(
                    self.slot_tables, carry, idx, pad
                )
            else:
                self.slot_tables = self._set_tau_jit(self.slot_tables, idx, pad)
            models = list(self.models)
            for b in slots:
                models[b] = None
            self.models = tuple(models)
        return carry

    def _build_set_tau(self) -> Callable:
        def write(tabs, idx, rows):
            return dict(
                tabs,
                tau_J=tabs["tau_J"].at[idx].set(rows, mode="drop"),
                tau_J2=tabs["tau_J2"].at[idx].set(f32(2.0) * rows, mode="drop"),
            )

        mesh = self.mesh
        if self.rung != "a4":
            kw = {"out_shardings": self._table_shardings()} if mesh else {}
            return jax.jit(write, **kw)
        n = self.model.n

        def write_rebuild(tabs, carry, idx, rows):
            spins = carry.spins.at[idx].get(mode="fill", fill_value=0.0)
            h_tau = jax.vmap(lambda sp, t: metropolis.lane_h_tau(sp, t, n))(
                spins, rows
            )
            carry = carry._replace(
                h_tau=carry.h_tau.at[idx].set(h_tau, mode="drop")
            )
            return write(tabs, idx, rows), carry

        kw = (
            {"out_shardings": (self._table_shardings(), self._carry_shardings())}
            if mesh
            else {}
        )
        return jax.jit(write_rebuild, **kw)


# -----------------------------------------------------------------------------
# jnp backend: vmapped pure sweep functions + host-side bulk RNG.
# -----------------------------------------------------------------------------


def _build_jnp(eng: SweepEngine) -> Callable:
    m, t = eng.model, eng.tables
    exp_flavor = eng.exp_flavor
    N = m.num_spins

    if eng.rung == "a1":
        def one(spins, hs, ht, beta, u):
            return metropolis.sweep_original(
                metropolis.FlatState(spins, hs, ht),
                t["graph_edges"], t["J"], t["is_tau"], t["incident"],
                u, beta, exp_flavor,
            )
        count = N
    elif eng.rung == "a2":
        def one(spins, hs, ht, beta, u):
            return metropolis.sweep_flat(
                metropolis.FlatState(spins, hs, ht),
                t["targets"], t["J2"], u, beta, m.space_degree, exp_flavor,
            )
        count = N
    elif eng.rung == "cb":
        # The colored sweep never reads the carried fields (it recomputes
        # h_eff from spins per class), so the per-sweep scan carries only
        # (spins, rng) and the dense `lane_h_eff` refresh of the carry
        # fields runs ONCE per run — a pure function of the final spins,
        # exactly like the fused kernel (`_make_colored_body`), so the
        # backends stay bit-identical.
        classes = t["classes"]
        exp_fn = metropolis.EXP_FNS[exp_flavor]
        count, B_, V_ = t["rows"], eng.batch, eng.V

        def flip_one(spins, beta, u):
            return metropolis.colored_flip_spins(spins, u, beta, classes, exp_fn)

        def run_cb(carry: SweepCarry, num_sweeps: int) -> SweepCarry:
            def sweep_once(sc, _):
                spins, rng = sc
                rng, u = mt.mt_uniforms_count(rng, count)
                u = u.reshape(count, B_, V_).transpose(1, 0, 2)
                return (jax.vmap(flip_one)(spins, carry.betas, u), rng), None

            (spins, rng), _ = lax.scan(
                sweep_once, (carry.spins, carry.rng), None, length=num_sweeps
            )
            hs, ht = jax.vmap(
                lambda sp: metropolis.lane_h_eff(
                    sp, t["h"], t["base_nbr"], t["base_J"], t["tau_J"], m.n
                )
            )(spins)
            return SweepCarry(spins, hs, ht, carry.betas, rng)

        return run_cb
    else:
        scalar_updates = eng.rung == "a3"

        def one(spins, hs, ht, beta, u):
            return metropolis.sweep_lane(
                metropolis.LaneState(spins, hs, ht),
                t["base_nbr"], t["base_J2"], t["tau_J2"],
                u, beta, m.n, exp_flavor, scalar_updates=scalar_updates,
            )
        count = t["rows"]

    B, lane = eng.batch, eng.rung in LANE_RUNGS
    V = eng.V

    def sweep_once(carry: SweepCarry) -> SweepCarry:
        rng, u = mt.mt_uniforms_count(carry.rng, count)
        if lane:
            u = u.reshape(count, B, V).transpose(1, 0, 2)  # (B, rows, V)
        else:
            u = u.T  # (B, N)
        st = jax.vmap(one)(carry.spins, carry.h_space, carry.h_tau, carry.betas, u)
        return SweepCarry(st.spins, st.h_space, st.h_tau, carry.betas, rng)

    def run(carry: SweepCarry, num_sweeps: int) -> SweepCarry:
        return lax.scan(
            lambda c, _: (sweep_once(c), None), carry, None, length=num_sweeps
        )[0]

    return run


# -----------------------------------------------------------------------------
# pallas backend: fused RNG + multi-sweep batched kernel, one launch per run.
# -----------------------------------------------------------------------------


def _build_pallas(eng: SweepEngine) -> Callable:
    from repro.kernels import ops

    m, t = eng.model, eng.tables

    if eng.rung == "cb":
        colored_fn = ops.make_colored_multisweep(
            t["classes"],
            m.h,
            m.space_nbr,
            m.space_J,
            m.tau_J,
            n=m.n,
            exp_flavor=eng.exp_flavor,
            interpret=eng.interpret,
            replica_tile=eng.replica_tile,
        )

        def run_cb(carry: SweepCarry, num_sweeps: int) -> SweepCarry:
            spins, hs, ht, rng = colored_fn(
                carry.spins, carry.rng, carry.betas, num_sweeps
            )
            return SweepCarry(spins, hs, ht, carry.betas, rng)

        return run_cb

    def run(carry: SweepCarry, num_sweeps: int) -> SweepCarry:
        spins, hs, ht, rng = ops.metropolis_multisweep(
            carry.spins,
            carry.h_space,
            carry.h_tau,
            carry.rng,
            t["base_nbr"],
            t["base_J2"],
            t["tau_J2"],
            carry.betas,
            n=m.n,
            num_sweeps=num_sweeps,
            exp_flavor=eng.exp_flavor,
            interpret=eng.interpret,
            replica_tile=eng.replica_tile,
        )
        return SweepCarry(spins, hs, ht, carry.betas, rng)

    return run


# -----------------------------------------------------------------------------
# Multi-tenant builders: identical sweep math, coupling tables as ARGUMENTS.
#
# The per-rung sweep functions already take their tables as parameters
# (core/metropolis.py), so the multi flavour is the same function vmapped
# over one extra axis: per-slot tables of shape [B, ...] map alongside the
# carry rows.  With B identical table copies every per-slot op is the same
# elementwise/gather op on the same values as the single-model path, which
# is why homogeneous multi-tenant serving is bit-identical to `build`
# (tests/test_multi_tenant.py) — there is no "almost the same" float path.
# -----------------------------------------------------------------------------


def _build_jnp_multi(eng: SweepEngine) -> Callable:
    m, t = eng.model, eng.tables
    exp_flavor = eng.exp_flavor
    count, B, V = t["rows"], eng.batch, eng.V

    if eng.rung == "cb":
        classes = t["classes"]
        exp_fn = metropolis.EXP_FNS[exp_flavor]

        def flip_one(spins, beta, u, *cls_tabs):
            # Reassemble per-replica classes from the pre-gathered coupling
            # slices; structural leaves stay trace-time constants.
            bound = metropolis.bind_class_tables(classes, cls_tabs)
            return metropolis.colored_flip_spins(spins, u, beta, bound, exp_fn)

        def run_cb(carry: SweepCarry, tabs: dict, num_sweeps: int) -> SweepCarry:
            h_b, bJ_b, tJ_b = tabs["h"], tabs["base_J"], tabs["tau_J"]
            # Gathered ONCE per run — loop-invariant, must not ride the
            # per-sweep scan (same values as the single-model constants,
            # hence still bit-identical).
            cls_tabs_b = metropolis.class_coupling_slices(
                classes, h_b, bJ_b, tJ_b, m.n
            )

            def sweep_once(sc, _):
                spins, rng = sc
                rng, u = mt.mt_uniforms_count(rng, count)
                u = u.reshape(count, B, V).transpose(1, 0, 2)
                spins = jax.vmap(flip_one)(
                    spins, carry.betas, u, *cls_tabs_b
                )
                return (spins, rng), None

            (spins, rng), _ = lax.scan(
                sweep_once, (carry.spins, carry.rng), None, length=num_sweeps
            )
            hs, ht = jax.vmap(
                lambda sp, h, bJ, tJ: metropolis.lane_h_eff(
                    sp, h, t["base_nbr"], bJ, tJ, m.n
                )
            )(spins, h_b, bJ_b, tJ_b)
            return SweepCarry(spins, hs, ht, carry.betas, rng)

        return run_cb

    def one(spins, hs, ht, beta, u, j2, tau2):
        return metropolis.sweep_lane(
            metropolis.LaneState(spins, hs, ht),
            t["base_nbr"], j2, tau2, u, beta, m.n, exp_flavor,
        )

    def run(carry: SweepCarry, tabs: dict, num_sweeps: int) -> SweepCarry:
        j2_b, tau2_b = tabs["base_J2"], tabs["tau_J2"]

        def sweep_once(c: SweepCarry, _):
            rng, u = mt.mt_uniforms_count(c.rng, count)
            u = u.reshape(count, B, V).transpose(1, 0, 2)  # (B, rows, V)
            st = jax.vmap(one)(
                c.spins, c.h_space, c.h_tau, c.betas, u, j2_b, tau2_b
            )
            return SweepCarry(st.spins, st.h_space, st.h_tau, c.betas, rng), None

        return lax.scan(sweep_once, carry, None, length=num_sweeps)[0]

    return run


def _build_pallas_multi(eng: SweepEngine) -> Callable:
    from repro.kernels import ops

    m, t = eng.model, eng.tables

    if eng.rung == "cb":
        colored_fn = ops.make_colored_multisweep_multi(
            t["classes"],
            m.space_nbr,
            n=m.n,
            exp_flavor=eng.exp_flavor,
            interpret=eng.interpret,
            replica_tile=eng.replica_tile,
        )

        def run_cb(carry: SweepCarry, tabs: dict, num_sweeps: int) -> SweepCarry:
            spins, hs, ht, rng = colored_fn(
                carry.spins, carry.rng, carry.betas,
                tabs["h"], tabs["base_J"], tabs["tau_J"], num_sweeps,
            )
            return SweepCarry(spins, hs, ht, carry.betas, rng)

        return run_cb

    def run(carry: SweepCarry, tabs: dict, num_sweeps: int) -> SweepCarry:
        spins, hs, ht, rng = ops.metropolis_multisweep_multi(
            carry.spins,
            carry.h_space,
            carry.h_tau,
            carry.rng,
            t["base_nbr"],
            tabs["base_J2"],
            tabs["tau_J2"],
            carry.betas,
            n=m.n,
            num_sweeps=num_sweeps,
            exp_flavor=eng.exp_flavor,
            interpret=eng.interpret,
            replica_tile=eng.replica_tile,
        )
        return SweepCarry(spins, hs, ht, carry.betas, rng)

    return run


register_backend("jnp", _build_jnp)
register_backend("pallas", _build_pallas)
register_multi_backend("jnp", _build_jnp_multi)
register_multi_backend("pallas", _build_pallas_multi)
