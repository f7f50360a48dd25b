"""Parallel tempering over a ladder of inverse temperatures (paper §1, [16][17]).

The paper's production context runs 115 replicas of each Ising model at
different temperatures and periodically proposes swaps between adjacent
temperatures.  Replicas are the engine's batch dimension: each round's
sweeps run through `SweepEngine.run`, so with ``backend="pallas"`` the
whole 115-replica sweep phase is a SINGLE fused kernel launch per round
(in-kernel RNG, multi-sweep grid loop) instead of a Python-level vmap with
host-side RNG reshuffling; with ``backend="jnp"`` it is one vmapped jit.
Swaps exchange *betas* (equivalently, exchange replica labels), the
standard O(1) formulation — spins stay put.

Swap rule for adjacent replicas (a, b):  accept with probability
``min(1, exp((beta_a - beta_b) * (E_a - E_b)))`` — computed with the same
fastexp used for flips, clamped >= 1 for favourable swaps.

Swap randomness: exactly ``ceil(R/2)`` fresh uniforms are drawn per round
(`draw_swap_uniforms`), one per candidate pair.  The previous scheme
indexed one 624-entry block modulo 624, which silently reused (and thus
correlated) pair uniforms whenever R > 2*624.

The pieces are deliberately separable: `swap_phase` (jitted, operates on
a PTState) and `energy_tables` are public so the serving layer can express
a whole tempering workload as one multi-slot job — `serve_mc.PTJob` packs
its R replicas into R slots of the shared resident engine, and a
tempering round becomes "one scheduled chunk + this swap_phase", sharing
fused launches with whatever else is resident (see DESIGN.md §Service).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine as sweep_engine
from repro.core import ising, mt19937
from repro.core.fastexp import EXP_FNS

f32 = jnp.float32


class PTState(NamedTuple):
    spins: jax.Array  # (R, rows, V)
    h_space: jax.Array  # (R, rows, V)
    h_tau: jax.Array  # (R, rows, V)
    betas: jax.Array  # (R,) current beta per replica slot
    rng: jax.Array  # (624, R*V) interlaced generator state (engine layout)
    swap_rng: jax.Array  # (624,) scalar generator for swap decisions
    swap_accept: jax.Array  # () int32 counter
    swap_propose: jax.Array  # () int32 counter


def make_pt_engine(
    m: ising.LayeredModel,
    num_replicas: int,
    *,
    V: int = 4,
    rung: str = "a4",
    backend: str = "jnp",
    exp_flavor: str = "fast",
    interpret: bool | None = None,
    replica_tile: int | None = None,
) -> sweep_engine.SweepEngine:
    """The batched lane-rung engine that owns the sweep phase of every PT
    round (``rung="a4"`` sequential order, ``rung="cb"`` colored order —
    any registered lane rung works, the swap phase only reads spins).

    ``backend="pallas"`` forces V to the kernel's 128-lane layout (the
    model's L must be a multiple of 2*128); ``replica_tile`` sizes the
    kernel's resident replica group to VMEM (must divide the replica
    count).
    """
    if backend == "pallas":
        from repro.kernels import ops

        V = ops.LANES
    return sweep_engine.SweepEngine.create(
        m,
        rung=rung,
        backend=backend,
        batch=num_replicas,
        V=V,
        exp_flavor=exp_flavor,
        interpret=interpret,
        replica_tile=replica_tile,
    )


def init_pt(
    m: ising.LayeredModel,
    betas: np.ndarray,
    *,
    V: int = 4,
    seed: int = 0,
    engine: sweep_engine.SweepEngine | None = None,
) -> PTState:
    eng = engine or make_pt_engine(m, len(betas), V=V)
    carry = eng.init_carry(seed=seed, betas=np.asarray(betas, np.float32))
    return PTState(
        carry.spins,
        carry.h_space,
        carry.h_tau,
        carry.betas,
        carry.rng,
        swap_rng=mt19937.mt_init(seed + 17),
        swap_accept=jnp.int32(0),
        swap_propose=jnp.int32(0),
    )


def lane_energy(
    spins: jax.Array,  # (rows, V)
    h: jax.Array,  # (n,) local fields
    base_nbr: jax.Array,
    base_J: jax.Array,  # (n, SD) NOT doubled
    tau_J: jax.Array,  # (n,)
    n: int,
) -> jax.Array:
    """Energy of one lane-layout replica (fully vectorized, no loop over rows).

    Each spin's terms are summed elementwise, then the spins' sums are
    added in one fixed pairwise order (`_pairwise_sum`).  A plain
    ``jnp.sum`` leaves the order to the compiler, and on a TPU it changes
    with the batch this function is vmapped over, so a ladder's swap
    decisions would depend on how many slots share a device.
    """
    rows, V = spins.shape
    lpv = rows // n
    s = spins.reshape(lpv, n, V)
    t = h[None, :, None] * s
    # Space terms: each undirected edge counted twice -> halve.
    for d in range(base_nbr.shape[1]):
        t = t + 0.5 * base_J[None, :, d, None] * s * s[:, base_nbr[:, d], :]
    # Tau terms: neighbour is next row-block; on the last block the next
    # layer is the first block of lane v+1 (global wrap lane V-1 -> 0).
    up = jnp.concatenate([s[1:], jnp.roll(s[:1], -1, axis=-1)], axis=0)
    t = t + tau_J[None, :, None] * s * up
    return -_pairwise_sum(t)


def _pairwise_sum(x: jax.Array) -> jax.Array:
    """Sum of all elements as a balanced tree of elementwise adds: zero
    padded to a power of two, then halved until one element is left."""
    x = jnp.ravel(x)
    size = 1 << max(x.size - 1, 0).bit_length()
    x = jnp.pad(x, (0, size - x.size))
    while x.size > 1:
        x = x[: x.size // 2] + x[x.size // 2 :]
    return x[0]


def draw_swap_uniforms(swap_rng: jax.Array, num_replicas: int):
    """Exactly ``ceil(R/2)`` fresh uniforms, one per candidate swap pair.

    Generates whole 624-entry MT19937 blocks (the generator's granularity)
    and returns only the first ``ceil(R/2)`` values; the tail is discarded,
    never reused — so no two pairs in a round can share a uniform.
    """
    npairs = (num_replicas + 1) // 2
    return mt19937.mt_uniforms_count(swap_rng, npairs)


def _swap_decide(
    betas: jax.Array,  # (R,)
    energies: jax.Array,  # (R,)
    swap_rng: jax.Array,
    swap_accept: jax.Array,
    swap_propose: jax.Array,
    swap_parity: jax.Array,
    exp_fn,
):
    """The swap decision given per-replica energies — the single body both
    `swap_phase` (which computes energies itself) and
    `swap_phase_from_energies` (which receives them, e.g. gathered from a
    mesh-sharded engine) run, so the two entry points are bit-identical by
    construction.  Returns (betas, swap_rng, swap_accept, swap_propose)."""
    R = betas.shape[0]
    swap_rng, su = draw_swap_uniforms(swap_rng, R)
    # Propose swaps between (i, i+1) for i of the given parity.
    idx = jnp.arange(R)
    is_left = (idx % 2 == swap_parity) & (idx + 1 < R)
    partner = jnp.where(
        is_left, idx + 1, jnp.where((idx % 2) != swap_parity, idx - 1, idx)
    )
    partner = jnp.clip(partner, 0, R - 1)
    valid = partner != idx
    d_beta = betas - betas[partner]
    d_e = energies - energies[partner]
    p_acc = exp_fn(jnp.clip(d_beta * d_e, -20.0, 0.0))  # min(1, exp(.))
    u_pair = su[idx // 2]  # one fresh uniform per pair, no index wrap
    u_pair = jnp.where(is_left, u_pair, u_pair[partner])  # shared within pair
    accept = valid & (u_pair < p_acc)
    # Betas move between replica slots; spins stay put.
    new_betas = jnp.where(accept, betas[partner], betas)
    n_acc = jnp.sum(accept.astype(jnp.int32)) // 2
    n_prop = jnp.sum((valid & is_left).astype(jnp.int32))
    return new_betas, swap_rng, swap_accept + n_acc, swap_propose + n_prop


@functools.partial(jax.jit, static_argnames=("n", "exp_flavor"))
def swap_phase(
    state: PTState,
    base_nbr: jax.Array,
    base_J: jax.Array,  # (n, SD) NOT doubled
    tau_J: jax.Array,  # (n,)
    h: jax.Array,
    swap_parity: jax.Array,  # 0 or 1: which adjacent pairs are proposed
    n: int,
    exp_flavor: str = "fast",
) -> PTState:
    """One even/odd round of adjacent-temperature swap proposals."""
    energies = jax.vmap(
        lambda s: lane_energy(s, h, base_nbr, base_J, tau_J, n)
    )(state.spins)
    betas, swap_rng, acc, prop = _swap_decide(
        state.betas, energies, state.swap_rng, state.swap_accept,
        state.swap_propose, swap_parity, EXP_FNS[exp_flavor],
    )
    return state._replace(
        betas=betas, swap_rng=swap_rng, swap_accept=acc, swap_propose=prop
    )


@functools.partial(jax.jit, static_argnames=("exp_flavor",))
def swap_phase_from_energies(
    betas: jax.Array,  # (R,)
    energies: jax.Array,  # (R,) per-replica energies of the current spins
    swap_rng: jax.Array,
    swap_accept: jax.Array,
    swap_propose: jax.Array,
    swap_parity: jax.Array,
    exp_flavor: str = "fast",
):
    """`swap_phase` for callers that already hold per-replica energies —
    the cross-device path: a mesh-sharded engine computes energies
    device-locally (`SweepEngine.slot_energies`), only the (R,) scalars
    cross devices, and this decides the beta exchanges.  Same `_swap_decide`
    body as `swap_phase`, so a ladder spanning devices swaps bit-identically
    to a resident single-device one.  Returns
    ``(betas, swap_rng, swap_accept, swap_propose)``."""
    return _swap_decide(
        betas, energies, swap_rng, swap_accept, swap_propose, swap_parity,
        EXP_FNS[exp_flavor],
    )


def energy_tables(eng: sweep_engine.SweepEngine):
    """(base_nbr, base_J, tau_J, h) for energy evaluation — built once with
    the engine's other model tables, so per-round calls neither re-halve
    couplings nor re-upload h."""
    t = eng.tables
    return t["base_nbr"], t["base_J"], t["tau_J"], t["h"]


def model_energy_tables(m: ising.LayeredModel):
    """(base_nbr, base_J, tau_J, h) built directly from a model — same
    arrays `energy_tables` yields for that model's own engine.  For
    consumers whose model is NOT the engine's (a multi-tenant `PTJob`
    swapping over a job-private model); build once per job, not per round.
    """
    return (
        jnp.asarray(m.space_nbr),
        jnp.asarray(m.space_J),
        jnp.asarray(m.tau_J),
        jnp.asarray(m.h),
    )


def pt_round(
    eng: sweep_engine.SweepEngine,
    state: PTState,
    swap_parity,
    sweeps_per_round: int = 1,
) -> PTState:
    """``sweeps_per_round`` engine sweeps on every replica — one batched
    (kernel) launch — then one even/odd round of swap proposals."""
    carry = sweep_engine.SweepCarry(
        state.spins, state.h_space, state.h_tau, state.betas, state.rng
    )
    carry = eng.run(carry, sweeps_per_round)
    state = state._replace(
        spins=carry.spins, h_space=carry.h_space, h_tau=carry.h_tau, rng=carry.rng
    )
    base_nbr, base_J, tau_J, h = energy_tables(eng)
    return swap_phase(
        state,
        base_nbr,
        base_J,
        tau_J,
        h,
        jnp.asarray(swap_parity, jnp.int32),
        eng.model.n,
        eng.exp_flavor,
    )


def run_parallel_tempering(
    m: ising.LayeredModel,
    betas: np.ndarray,
    num_rounds: int,
    *,
    V: int = 4,
    seed: int = 0,
    sweeps_per_round: int = 1,
    exp_flavor: str = "fast",
    rung: str = "a4",
    backend: str = "jnp",
    interpret: bool | None = None,
):
    """Driver: returns (final PTState, per-slot energies).

    ``backend="pallas"`` runs each round's sweep phase as one fused
    multi-sweep batched kernel launch (V is forced to the 128-lane layout
    by `make_pt_engine`, so the model needs L % 256 == 0);
    ``backend="jnp"`` is the vmapped host path.  ``rung="cb"`` swaps the
    sweep phase to the graph-colored chain (same equilibrium, faster
    per sweep on wide hardware).
    """
    eng = make_pt_engine(
        m, len(betas), V=V, rung=rung, backend=backend, exp_flavor=exp_flavor,
        interpret=interpret,
    )
    state = init_pt(m, betas, seed=seed, engine=eng)
    for r in range(num_rounds):
        state = pt_round(eng, state, r % 2, sweeps_per_round)
    base_nbr, base_J, tau_J, h = energy_tables(eng)
    energies = jax.vmap(
        lambda s: lane_energy(s, h, base_nbr, base_J, tau_J, m.n)
    )(state.spins)
    return state, np.asarray(energies)
