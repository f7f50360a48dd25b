"""The Metropolis sweep ladder (paper Table 1): PURE per-rung sweep functions.

This module is intentionally driver-free.  Each function advances one
replica by exactly one sweep, consuming a caller-provided buffer of
uniforms (one per spin visit — the paper's bulk-RNG "result caching",
§2.3).  Everything else the old drivers did — model-array setup, RNG
plumbing, the sweep loop, batching over replicas, backend choice — now
lives in ONE place, `repro.core.engine.SweepEngine`, which dispatches to
these functions (``backend="jnp"``) or to the fused Pallas kernel
(``backend="pallas"``; kernels/metropolis_kernel.py).  See DESIGN.md
§Engine for the architecture and §RNG fusion for where uniforms are
generated per backend and why the two backends stay bit-exact.

Every sweep function takes its model tables (couplings, fields,
neighbours) as ARGUMENTS rather than closing over them — the property the
multi-tenant engine path relies on: the same function body serves one
model (tables broadcast) or B different models (tables vmapped per slot,
`SweepEngine.build_multi`) with bit-identical per-slot floats.

Every implementation level of the paper is reproduced with the *same
semantics* expressed over its own memory layout, so rungs can be compared
both for bit-exactness (same exp flavour, same uniforms) and for wall-clock
(the benchmark harness):

  A.1  ``sweep_original``   — edge-centric structures of Figure 4; the
       neighbour select and tau/space select of Figure 2; 2*S_mul*J
       recomputed per edge (no result caching); exact exp by default.
  A.2  ``sweep_flat``       — simplified per-spin layout of Figure 5/6
       (pre-doubled J, tau edges last), bulk RNG, fastexp.
  A.3  ``sweep_lane(..., scalar_updates=True)`` — vectorized RNG+flip
       probability, scalar neighbour updates.
  A.4  ``sweep_lane``       — fully vectorized: V-lane interlaced layout
       (reorder.py), masked vector flips, whole-row neighbour updates,
       lane-rotated wrap rows as the special case.

One rung goes beyond the paper's ladder:

  cb   ``sweep_colored``    — graph-colored sublattice order: the lane
       rows are grouped into C conflict-free color classes
       (reorder.colored_classes) and one sweep is C whole-lattice masked
       vector updates instead of ``rows`` sequential row steps.  Same
       Boltzmann stationary distribution, DIFFERENT chain — not
       bit-comparable to a1-a4 (DESIGN.md §Coloring), but bit-exact
       across backends within the rung and it consumes the identical
       per-sweep uniform stream as a4.

Hardware note (DESIGN.md §Adaptation): branch elimination (§2.1) has no
direct JAX analogue — XLA always lowers to select/mask — so the A.1->A.2
delta here measures the data-structure and caching effects only.

``make_sweeper`` and ``run_sweeps`` remain as DEPRECATED thin shims over
the engine (one release); new code should construct a `SweepEngine`
directly.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import ising, reorder
from repro.core.fastexp import EXP_FNS

f32 = jnp.float32


class FlatState(NamedTuple):
    spins: jax.Array  # (N,) float32 in {-1, +1}
    h_space: jax.Array  # (N,) float32, includes local field h
    h_tau: jax.Array  # (N,) float32


class LaneState(NamedTuple):
    spins: jax.Array  # (rows, V)
    h_space: jax.Array  # (rows, V)
    h_tau: jax.Array  # (rows, V)


def make_flat_state(m: ising.LayeredModel, spins: np.ndarray) -> FlatState:
    hs, ht = ising.h_eff_from_scratch(m, spins)
    return FlatState(jnp.asarray(spins, f32), jnp.asarray(hs), jnp.asarray(ht))


def make_lane_state(m: ising.LayeredModel, spins: np.ndarray, V: int) -> LaneState:
    hs, ht = ising.h_eff_from_scratch(m, spins)
    lane = lambda x: jnp.asarray(reorder.to_lane(x, m.n, m.L, V))
    return LaneState(lane(np.asarray(spins, np.float32)), lane(hs), lane(ht))


def _flip(s, h_sum, u, beta, exp_fn):
    """Metropolis accept test; returns (S_mul = s*mask, new spin).

    p = exp(-2 beta s h_eff); accept if u < p.  The identical expression is
    used by every rung so layouts can be compared bit-exactly.
    """
    x = (f32(-2.0) * f32(beta)) * s * h_sum
    p = exp_fn(x)
    mask = (u < p).astype(f32)
    return s * mask, s * (f32(1.0) - f32(2.0) * mask)


# -----------------------------------------------------------------------------
# A.1 — original edge-centric structures (Figure 2 / Figure 4).
# -----------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("exp_flavor", "num_incident"))
def sweep_original(
    state: FlatState,
    graph_edges: jax.Array,  # (E, 2) int32
    J: jax.Array,  # (E,) float32 (NOT pre-doubled)
    is_tau: jax.Array,  # (E,) bool
    incident: jax.Array,  # (N, D) int32 edge ids
    u: jax.Array,  # (N,) uniforms
    beta: float,
    exp_flavor: str = "exact",
    num_incident: int | None = None,
) -> FlatState:
    exp_fn = EXP_FNS[exp_flavor]
    D = incident.shape[1] if num_incident is None else num_incident

    def spin_step(t, carry):
        spins, hs, ht = carry
        s = spins[t]
        smul, s_new = _flip(s, hs[t] + ht[t], u[t], beta, exp_fn)

        def edge_step(d, hsht):
            hs, ht = hsht
            e = incident[t, d]
            ends = graph_edges[e]
            # Figure 3: branch-free neighbour select via comparison-as-index.
            nbr = ends[(ends[0] == t).astype(jnp.int32)]
            val = f32(2.0) * smul * J[e]  # recomputed every edge (A.1 style)
            tau = is_tau[e]
            hs = hs.at[nbr].add(jnp.where(tau, f32(0.0), -val))
            ht = ht.at[nbr].add(jnp.where(tau, -val, f32(0.0)))
            return hs, ht

        hs, ht = lax.fori_loop(0, D, edge_step, (hs, ht))
        return spins.at[t].set(s_new), hs, ht

    out = lax.fori_loop(0, state.spins.shape[0], spin_step, tuple(state))
    return FlatState(*out)


# -----------------------------------------------------------------------------
# A.2 — simplified per-spin layout (Figure 5/6): tau edges are the last two
# slots, J pre-doubled, one fused update line.
# -----------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("exp_flavor", "space_degree"))
def sweep_flat(
    state: FlatState,
    targets: jax.Array,  # (N, D) int32
    J2: jax.Array,  # (N, D) float32, pre-doubled
    u: jax.Array,  # (N,)
    beta: float,
    space_degree: int,
    exp_flavor: str = "fast",
) -> FlatState:
    exp_fn = EXP_FNS[exp_flavor]
    sd = space_degree

    def spin_step(t, carry):
        spins, hs, ht = carry
        s = spins[t]
        smul, s_new = _flip(s, hs[t] + ht[t], u[t], beta, exp_fn)
        contrib = -smul * J2[t]  # == -= 2*S_mul*J with J pre-doubled
        hs = hs.at[targets[t, :sd]].add(contrib[:sd])
        ht = ht.at[targets[t, sd:]].add(contrib[sd:])
        return spins.at[t].set(s_new), hs, ht

    out = lax.fori_loop(0, state.spins.shape[0], spin_step, tuple(state))
    return FlatState(*out)


# -----------------------------------------------------------------------------
# A.3 / A.4 — lane-interlaced vectorized sweep (Figure 12b, §3.1).
# -----------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("n", "exp_flavor", "scalar_updates")
)
def sweep_lane(
    state: LaneState,
    base_nbr: jax.Array,  # (n, SD) int32 in-layer neighbour site ids
    base_J2: jax.Array,  # (n, SD) float32, pre-doubled (identical every layer)
    tau_J2: jax.Array,  # (n,) float32, pre-doubled
    u: jax.Array,  # (rows, V) uniforms
    beta: float,
    n: int,
    exp_flavor: str = "fast",
    scalar_updates: bool = False,
) -> LaneState:
    """One vectorized Metropolis sweep over the lane-interlaced layout.

    All V lanes of a row flip together (they are mutually non-adjacent by
    construction).  Space neighbours of a row form whole rows; tau
    neighbours are rows +-n, same lane, except in the first/last layer block
    where the contribution rotates across lanes (wrap between sections).
    ``scalar_updates=True`` degrades the neighbour updates to a per-lane
    loop — the paper's A.3 rung (vector RNG+flip, scalar updates).
    """
    exp_fn = EXP_FNS[exp_flavor]
    rows, V = state.spins.shape
    sd = base_nbr.shape[1]

    def scatter_add(arr, row, contrib):
        if scalar_updates:
            def lane_step(v, a):
                return a.at[row, v].add(contrib[v])
            return lax.fori_loop(0, V, lane_step, arr)
        return arr.at[row].add(contrib)

    def row_step(q, carry, wrap):
        spins, hs, ht = carry
        s = spins[q]
        smul, s_new = _flip(s, hs[q] + ht[q], u[q], beta, exp_fn)
        spins = spins.at[q].set(s_new)
        i = jnp.remainder(q, n)
        base = q - i
        nbrs = base_nbr[i]  # (SD,) same for every layer: the paper's
        j2 = base_J2[i]  # "topologically identical" exploitation
        for d in range(sd):  # static unroll, SD ~ 4-6
            hs = scatter_add(hs, base + nbrs[d], -smul * j2[d])
        tc = -smul * tau_J2[i]  # tau contribution, both directions
        if wrap == -1:  # first layer of each section: down-link wraps
            ht = scatter_add(ht, rows - n + i, jnp.roll(tc, -1))
            ht = scatter_add(ht, q + n, tc)
        elif wrap == +1:  # last layer of each section: up-link wraps
            ht = scatter_add(ht, q - n, tc)
            ht = scatter_add(ht, i, jnp.roll(tc, 1))
        else:
            ht = scatter_add(ht, q - n, tc)
            ht = scatter_add(ht, q + n, tc)
        return spins, hs, ht

    carry = tuple(state)
    carry = lax.fori_loop(0, n, functools.partial(row_step, wrap=-1), carry)
    carry = lax.fori_loop(n, rows - n, functools.partial(row_step, wrap=0), carry)
    carry = lax.fori_loop(rows - n, rows, functools.partial(row_step, wrap=+1), carry)
    return LaneState(*carry)


# -----------------------------------------------------------------------------
# "cb" — graph-colored sublattice sweep (beyond the paper's ladder).
#
# A.4 vectorizes *within* a spin visit but still walks the rows
# sequentially, so its hot loop is `rows` tiny (1, V) ops with a serial
# dependency.  The colored rung removes the serial walk: the lane-layout
# rows are grouped into C conflict-free color classes (reorder.colored_classes,
# C ~ 2-4), and one sweep is C whole-lattice vector updates — per class,
# recompute the class rows' effective fields by pure gathers from the
# current spins, flip all of them with one masked vector op, write back.
# Updating a conflict-free class in one shot is equivalent to updating its
# rows sequentially in any order (they do not interact), so each class
# update satisfies detailed balance and the composed chain has the same
# Boltzmann stationary distribution as the sequential sweep — but it is a
# DIFFERENT chain and cannot be bit-compared to a1-a4 (DESIGN.md §Coloring).
#
# There are no scatter-adds anywhere (additions would need a defined order
# to be reproducible): fields are *recomputed* from spins — per class for
# the rows being flipped, densely for the whole lattice at sweep end so the
# carried h_space/h_tau stay consistent.  That makes every operation a
# deterministic elementwise/gather op, which is what lets the Pallas kernel
# vmap these exact functions and stay bit-identical to the jnp backend.
# -----------------------------------------------------------------------------


def lane_h_eff(
    spins: jax.Array,  # (rows, V)
    h: jax.Array,  # (n,)
    base_nbr: jax.Array,  # (n, SD)
    base_J: jax.Array,  # (n, SD) NOT doubled
    tau_J: jax.Array,  # (n,)
    n: int,
):
    """Dense recomputation of (h_space, h_tau) over the lane layout.

    Pure gathers/rolls, no row loop — the vectorized analogue of
    ``ising.h_eff_from_scratch``.  Section boundaries: the previous layer
    of a section-start row is the section-end row one lane over (roll +1),
    the next layer of a section-end row is the section-start row one lane
    over (roll -1) — the same wrap the sequential sweep special-cases.
    """
    rows, V = spins.shape
    lpv = rows // n
    s = spins.reshape(lpv, n, V)
    hs = jnp.broadcast_to(h[None, :, None].astype(f32), s.shape)
    for d in range(base_nbr.shape[1]):
        hs = hs + base_J[None, :, d, None] * s[:, base_nbr[:, d], :]
    return hs.reshape(rows, V), lane_h_tau(spins, tau_J, n)


def lane_h_tau(spins: jax.Array, tau_J: jax.Array, n: int) -> jax.Array:
    """The tau half of `lane_h_eff`: ``tau_J * (down + up)`` per spin of a
    (rows, V) lane tile.  Exact in float32 (``down + up`` is -2, 0 or 2),
    so a rebuild under new tau couplings equals the incremental fields a
    sweep would have carried under them."""
    rows, V = spins.shape
    s = spins.reshape(rows // n, n, V)
    down = jnp.concatenate([jnp.roll(s[-1:], 1, axis=-1), s[:-1]], axis=0)
    up = jnp.concatenate([s[1:], jnp.roll(s[:1], -1, axis=-1)], axis=0)
    return (tau_J[None, :, None] * (down + up)).reshape(rows, V)


def class_coupling_slices(classes, h_b, space_J_b, tau_J_b, n: int):
    """Pre-gather each class's coupling/field tables from BATCHED
    ``[B, n, ...]`` per-slot site tables (the multi-tenant path).

    Returns a flat list ``[h_0, space_J_0, tau_J_0, h_1, ...]`` of
    ``[B, k, ...]`` arrays, one triple per class.  Called ONCE per launch
    — the slot tables are loop-invariant, so these gathers must not ride
    the per-sweep loop — and consumed per replica via `bind_class_tables`
    under the replica vmap.  Works with host numpy classes (trace-time
    constants, jnp backend) and with traced leaves (inside the Pallas
    kernel body) alike.
    """
    out = []
    for cls in classes:
        i = cls.rows % n  # row (p, i) holds site i of every lane's layer p
        out += [h_b[:, i], space_J_b[:, i], tau_J_b[:, i]]
    return out


def bind_class_tables(classes, cls_tabs):
    """Rebind structural color classes to one replica's coupling slices
    (`class_coupling_slices` entries with the batch dim mapped away).

    Keeps each class's structural gather tables (rows, neighbour targets,
    tau sources, roll masks — a pure function of topology, shared by every
    model in a multi-tenant engine) and replaces its ``h``/``space_J``/
    ``tau_J`` leaves.  With the tables of the model the classes were
    built from, the bound leaves equal the precomputed ones value for
    value — which is what keeps the single-model and multi-model colored
    paths bit-identical.  Shared verbatim by the jnp backend and the
    Pallas kernel body, like `colored_flip_spins` itself.
    """
    return tuple(
        cls._replace(
            h=cls_tabs[3 * c],
            space_J=cls_tabs[3 * c + 1],
            tau_J=cls_tabs[3 * c + 2],
        )
        for c, cls in enumerate(classes)
    )


def colored_flip_spins(
    spins: jax.Array,  # (rows, V)
    u: jax.Array,  # (rows, V) uniforms, indexed by row id (the a4 stream)
    beta,
    classes,  # tuple of reorder.ColorClass (trace-time constants)
    exp_fn,
) -> jax.Array:
    """One colored sweep over the spins: C whole-lattice masked updates.

    Shared verbatim by the jnp backend (vmapped over replicas) and the
    Pallas kernel body (vmapped over the replica tile), so the two
    backends are bit-identical by construction.
    """
    for cls in classes:
        sc = spins[cls.rows]  # (k, V)
        hs_c = jnp.broadcast_to(jnp.asarray(cls.h, f32)[:, None], sc.shape)
        for d in range(cls.space_tgt.shape[1]):
            hs_c = hs_c + cls.space_J[:, d, None] * spins[cls.space_tgt[:, d]]
        down = spins[cls.down_src]
        down = jnp.where(cls.down_roll[:, None], jnp.roll(down, 1, axis=-1), down)
        up = spins[cls.up_src]
        up = jnp.where(cls.up_roll[:, None], jnp.roll(up, -1, axis=-1), up)
        ht_c = cls.tau_J[:, None] * (down + up)
        _, s_new = _flip(sc, hs_c + ht_c, u[cls.rows], beta, exp_fn)
        spins = spins.at[cls.rows].set(s_new)
    return spins


def sweep_colored(
    state: LaneState,
    classes,  # tuple of reorder.ColorClass
    h: jax.Array,  # (n,)
    base_nbr: jax.Array,  # (n, SD)
    base_J: jax.Array,  # (n, SD) NOT doubled
    tau_J: jax.Array,  # (n,)
    u: jax.Array,  # (rows, V) uniforms
    beta,
    n: int,
    exp_flavor: str = "fast",
) -> LaneState:
    """One colored Metropolis sweep; consumes the identical uniform buffer
    (one per row, indexed by row id) as `sweep_lane`, so the RNG stream
    position after k sweeps matches the a4 rung exactly.

    The incoming ``state.h_space``/``h_tau`` are ignored (fields are
    recomputed from spins); the returned fields are the dense
    `lane_h_eff` of the new spins, keeping the carry invariant.
    """
    exp_fn = EXP_FNS[exp_flavor]
    spins = colored_flip_spins(state.spins, u, beta, classes, exp_fn)
    hs, ht = lane_h_eff(spins, h, base_nbr, base_J, tau_J, n)
    return LaneState(spins, hs, ht)


# -----------------------------------------------------------------------------
# DEPRECATED shims: the drivers now live in repro.core.engine.SweepEngine.
# Kept for one release so existing callers keep working; both produce spins
# bit-identical to the engine path (tests/test_engine.py).
# -----------------------------------------------------------------------------

LADDER = ("a1", "a2", "a3", "a4")  # the paper's rungs; "cb" extends beyond


def make_sweeper(
    m: ising.LayeredModel,
    impl: str,
    *,
    num_sweeps: int = 1,
    seed: int = 1234,
    exp_flavor: str | None = None,
    V: int = 4,
):
    """DEPRECATED — use ``SweepEngine.create(...)`` + ``engine.run_fn``.

    Build (jitted_fn, initial_carry) for steady-state benchmarking.
    ``jitted_fn(carry) -> carry`` runs ``num_sweeps`` sweeps; the engine's
    persistent jit means repeated timing calls hit the compile cache.
    """
    from repro.core import engine as _engine

    eng = _engine.SweepEngine.create(
        m, rung=impl, backend="jnp", batch=1, V=V, exp_flavor=exp_flavor
    )
    carry0 = eng.init_carry(seed=seed, spins=ising.init_spins(m, seed))
    return eng.run_fn(num_sweeps), carry0


def run_sweeps(
    m: ising.LayeredModel,
    spins: np.ndarray,
    impl: str,
    num_sweeps: int,
    *,
    seed: int = 1234,
    exp_flavor: str | None = None,
    V: int = 4,
):
    """DEPRECATED — use ``SweepEngine.create(...)`` + ``engine.run``.

    Run ``num_sweeps`` Metropolis sweeps with the given ladder rung.
    Returns final spins in FLAT (layer-major) order regardless of rung, so
    results are directly comparable across the ladder.
    """
    from repro.core import engine as _engine

    eng = _engine.SweepEngine.create(
        m, rung=impl, backend="jnp", batch=1, V=V, exp_flavor=exp_flavor
    )
    carry = eng.init_carry(seed=seed, spins=np.asarray(spins))
    carry = eng.run(carry, num_sweeps)
    return eng.spins_flat(carry)[0], eng.state_of(carry, 0)
