"""Public jit'd entry points for the Pallas kernels.

``interpret=None`` auto-selects: Pallas compiled path on TPU backends,
interpret mode (Python-evaluated kernel bodies) everywhere else — this is
how the kernels are validated on CPU per the project contract.

Every entry point derives the batch extent from its input shapes (no
baked-in global B), which is what lets the mesh-sharded engine (DESIGN.md
§Mesh) reuse these kernels UNCHANGED as per-device ``shard_map`` bodies:
inside the map each device sees the local (B/D, ...) block and the kernel
neither knows nor cares that it is one shard of a larger slot pool.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ising, metropolis, reorder
from repro.kernels import fastexp_kernel, metropolis_kernel, mt19937_kernel

LANES = 128


def _auto_interpret(interpret):
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def fastexp(x: jax.Array, flavor: str = "fast", interpret=None) -> jax.Array:
    """Bit-trick exp on arbitrary-shaped f32 input (pads to lane tiles)."""
    interpret = _auto_interpret(interpret)
    flat = jnp.ravel(x)
    pad = (-flat.size) % LANES
    padded = jnp.pad(flat, (0, pad)) if pad else flat
    out = fastexp_kernel.fastexp_2d(
        padded.reshape(-1, LANES), flavor=flavor, interpret=interpret
    )
    return out.reshape(-1)[: flat.size].reshape(x.shape)


def mt_next_block(state: jax.Array, interpret=None):
    """Advance (624, V) interlaced MT19937 state; V padded to 128 multiple."""
    interpret = _auto_interpret(interpret)
    v = state.shape[1]
    pad = (-v) % LANES
    if pad:
        # Pad with dummy generators (state lanes of zeros are still valid
        # uint32 math; outputs on padded lanes are discarded).
        state = jnp.pad(state, ((0, 0), (0, pad)))
    new_state, out = mt19937_kernel.mt_next_block_kernel(state, interpret=interpret)
    return new_state[:, :v], out[:, :v]


def mt_uniform_blocks(state: jax.Array, num_blocks: int, interpret=None):
    """``num_blocks`` blocks of uniforms from (624, V) interlaced state, V a
    multiple of 128, via the fused twist+temper+convert kernel."""
    return mt19937_kernel.mt_uniform_blocks_kernel(
        state, num_blocks, interpret=_auto_interpret(interpret)
    )


def metropolis_sweep(
    spins,
    h_space,
    h_tau,
    u,
    base_nbr,
    base_J2,
    tau_J2,
    beta,
    n: int,
    exp_flavor: str = "fast",
    interpret=None,
):
    """DEPRECATED single-sweep entry (host-generated uniforms, one launch
    per sweep); kept one release for the launch-structure benchmark and the
    historical oracle tests.  Use `metropolis_multisweep` (fused RNG) or
    `make_colored_multisweep` (colored order) in new code."""
    interpret = _auto_interpret(interpret)
    return metropolis_kernel.metropolis_sweep_kernel(
        spins,
        h_space,
        h_tau,
        u,
        base_nbr,
        base_J2,
        tau_J2,
        beta,
        n,
        exp_flavor,
        interpret=interpret,
    )


def metropolis_multisweep(
    spins,
    h_space,
    h_tau,
    rng,
    base_nbr,
    base_J2,
    tau_J2,
    beta,
    n: int,
    num_sweeps: int,
    exp_flavor: str = "fast",
    interpret=None,
    replica_tile: int | None = None,
):
    """Fused batched sweep: in-kernel MT19937, ``num_sweeps`` sweeps, one
    launch for all B replicas; see metropolis_kernel.

    ``rng`` is the (624, B*128) interlaced generator state (replica b owns
    lane columns b*128..(b+1)*128), the engine's canonical layout.
    Returns (spins, h_space, h_tau, rng).
    """
    interpret = _auto_interpret(interpret)
    return metropolis_kernel.metropolis_multisweep_kernel(
        spins,
        h_space,
        h_tau,
        rng,
        base_nbr,
        base_J2,
        tau_J2,
        beta,
        n,
        num_sweeps,
        exp_flavor,
        interpret=interpret,
        replica_tile=replica_tile,
    )


def metropolis_multisweep_multi(
    spins,
    h_space,
    h_tau,
    rng,
    base_nbr,  # (n, SD) shared topology
    base_J2_b,  # (B, n, SD) per-slot doubled couplings
    tau_J2_b,  # (B, n) per-slot doubled tau couplings
    beta,
    n: int,
    num_sweeps: int,
    exp_flavor: str = "fast",
    interpret=None,
    replica_tile: int | None = None,
):
    """Multi-tenant fused batched sweep: like `metropolis_multisweep`, but
    each replica slot sweeps its OWN model's couplings (same lattice
    topology), shipped as ``[B, ...]`` batched kernel inputs.  Returns
    (spins, h_space, h_tau, rng).
    """
    interpret = _auto_interpret(interpret)
    return metropolis_kernel.metropolis_multisweep_multi_kernel(
        spins,
        h_space,
        h_tau,
        rng,
        base_nbr,
        base_J2_b,
        tau_J2_b,
        beta,
        n,
        num_sweeps,
        exp_flavor,
        interpret=interpret,
        replica_tile=replica_tile,
    )


def make_colored_multisweep(
    classes,
    h,
    base_nbr,
    base_J,
    tau_J,
    n: int,
    exp_flavor: str = "fast",
    interpret=None,
    replica_tile: int | None = None,
):
    """Build the fused graph-colored sweep entry (the "cb" rung) for one
    model: ``fn(spins, rng, beta, num_sweeps) -> (spins, h_space, h_tau,
    rng)`` with in-kernel MT19937 and ``num_sweeps`` static.

    ``classes`` is `reorder.colored_classes(model, 128)`; coupling tables
    are the UNDOUBLED model arrays (the colored sweep recomputes fields
    rather than incrementally updating them).  See metropolis_kernel.
    """
    interpret = _auto_interpret(interpret)
    return metropolis_kernel.make_colored_multisweep_kernel(
        classes,
        h,
        base_nbr,
        base_J,
        tau_J,
        n,
        exp_flavor,
        interpret=interpret,
        replica_tile=replica_tile,
    )


def make_colored_multisweep_multi(
    classes,
    base_nbr,
    n: int,
    exp_flavor: str = "fast",
    interpret=None,
    replica_tile: int | None = None,
):
    """Build the multi-tenant fused colored-sweep entry for one TOPOLOGY:
    ``fn(spins, rng, beta, h_b, base_J_b, tau_J_b, num_sweeps)`` with the
    per-slot (UNDOUBLED) coupling tables as runtime ``[B, ...]`` inputs —
    one compiled callable serves any model mix sharing the lattice of
    ``classes`` (`reorder.colored_classes` of any such model).
    """
    interpret = _auto_interpret(interpret)
    return metropolis_kernel.make_colored_multisweep_multi_kernel(
        classes,
        base_nbr,
        n,
        exp_flavor,
        interpret=interpret,
        replica_tile=replica_tile,
    )


def make_kernel_inputs(m: ising.LayeredModel, batch: int, *, seed: int = 0):
    """Build (spins, hs, ht, u, tables..., beta) kernel inputs for ``batch``
    replicas of model ``m`` with V=128 lane interlacing."""
    reorder.check_lane_shape(m.n, m.L, LANES)
    states = []
    rng = np.random.default_rng(seed)
    for b in range(batch):
        sp = ising.init_spins(m, seed=seed * 131 + b)
        states.append(metropolis.make_lane_state(m, sp, LANES))
    spins = jnp.stack([s.spins for s in states])
    hs = jnp.stack([s.h_space for s in states])
    ht = jnp.stack([s.h_tau for s in states])
    u = jnp.asarray(rng.random(spins.shape, dtype=np.float32))
    beta = jnp.full((batch,), m.beta, jnp.float32)
    return (
        spins,
        hs,
        ht,
        u,
        jnp.asarray(m.space_nbr),
        jnp.asarray(2.0 * m.space_J),
        jnp.asarray(2.0 * m.tau_J),
        beta,
    )
