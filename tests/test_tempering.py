"""Parallel tempering + QMC helpers."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ising, metropolis, qmc, tempering


def test_lane_energy_matches_reference():
    m = ising.random_layered_model(n=6, L=8, seed=3, beta=0.7)
    sp = ising.init_spins(m, 5)
    ls = metropolis.make_lane_state(m, sp, 4)
    e_lane = float(
        tempering.lane_energy(
            ls.spins, jnp.asarray(m.h), jnp.asarray(m.space_nbr),
            jnp.asarray(m.space_J), jnp.asarray(m.tau_J), m.n,
        )
    )
    assert abs(e_lane - ising.energy(m, sp)) < 1e-3 * max(1, abs(ising.energy(m, sp)))


def test_lane_energy_sum_order_is_fixed():
    """The energy is the same pairwise tree of f32 adds whatever batch it
    is vmapped over: the swap phase of a ladder spread over devices sums
    per-device batches, a resident ladder sums its own."""
    import jax

    m = ising.random_layered_model(n=6, L=8, seed=3, beta=0.7)
    tabs = [jnp.asarray(a) for a in (m.h, m.space_nbr, m.space_J, m.tau_J)]
    spins = np.stack([
        np.asarray(metropolis.make_lane_state(m, ising.init_spins(m, k), 4).spins)
        for k in range(6)
    ])
    energy = jax.jit(jax.vmap(lambda s: tempering.lane_energy(s, *tabs, m.n)))
    whole = np.asarray(energy(jnp.asarray(spins)))
    for b in range(6):
        np.testing.assert_array_equal(np.asarray(energy(jnp.asarray(spins[b:b + 1]))),
                                      whole[b:b + 1])
    # The tree itself: 13 values padded to 16, halved four times.
    x = np.arange(1, 14, dtype=np.float32) / np.float32(7)
    tree = np.concatenate([x, np.zeros(3, np.float32)])
    while tree.size > 1:
        tree = tree[: tree.size // 2] + tree[tree.size // 2 :]
    assert np.asarray(tempering._pairwise_sum(jnp.asarray(x))) == tree[0]


def test_pt_round_runs_and_swaps():
    m = ising.random_layered_model(n=6, L=8, seed=3)
    betas = np.linspace(0.2, 2.5, 8)
    state, energies = tempering.run_parallel_tempering(m, betas, 8, V=4, seed=2)
    assert int(state.swap_propose) > 0
    assert energies.shape == (8,)
    # The multiset of betas is preserved by swapping.
    np.testing.assert_allclose(
        np.sort(np.asarray(state.betas)), np.sort(betas.astype(np.float32)), rtol=1e-6
    )


def test_pt_cold_replica_reaches_lower_energy():
    m = ising.random_layered_model(n=8, L=8, seed=1)
    betas = np.array([0.1, 3.0])
    state, energies = tempering.run_parallel_tempering(
        m, betas, 20, V=4, seed=3, sweeps_per_round=2
    )
    cold = np.asarray(state.betas).argmax()
    hot = np.asarray(state.betas).argmin()
    assert energies[cold] < energies[hot]


def test_swap_uniforms_fresh_and_distinct_per_pair():
    """ceil(R/2) fresh uniforms per round; no modulo reuse even for
    R > 2*624 (the old indexing silently correlated those pairs)."""
    from repro.core import mt19937

    for R in (7, 8, 2000):  # odd, even, and > 2*624 replicas
        rng = mt19937.mt_init(123)
        rng2, su = tempering.draw_swap_uniforms(rng, R)
        assert su.shape == ((R + 1) // 2,)
        su_np = np.asarray(su)
        assert np.unique(su_np).size == su_np.size, "pair uniforms must be distinct"
        # Consecutive rounds draw fresh values (state advanced).
        _, su_next = tempering.draw_swap_uniforms(rng2, R)
        assert not np.array_equal(su_np, np.asarray(su_next))


def test_pt_round_engine_backends_agree():
    """One PT round (sweeps + swap bookkeeping) is bit-identical whether
    the sweep phase runs on the jnp path or the fused Pallas kernel."""
    m = ising.random_layered_model(n=4, L=256, seed=6, beta=1.0)
    betas = np.linspace(0.3, 2.0, 4)
    out = {}
    for backend in ("jnp", "pallas"):
        eng = tempering.make_pt_engine(m, len(betas), V=128, backend=backend)
        state = tempering.init_pt(m, betas, seed=4, engine=eng)
        for r in range(2):
            state = tempering.pt_round(eng, state, r % 2, sweeps_per_round=2)
        out[backend] = state
    for f in tempering.PTState._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(out["jnp"], f)),
            np.asarray(getattr(out["pallas"], f)),
            err_msg=f,
        )


def test_tau_coupling_monotonic_in_gamma():
    # Stronger transverse field -> weaker slice coupling.
    js = [qmc.tau_coupling(2.0, g, 32) for g in (0.5, 1.0, 2.0, 4.0)]
    assert all(a > b for a, b in zip(js, js[1:]))
    assert all(j > 0 for j in js)


def test_qmc_anneal_schedule_end_to_end():
    pb = qmc.random_problem(6, 8, seed=4)
    spins = ising.init_spins(pb.layered_model(2.0, 3.0), seed=0)
    energies = []
    for beta, gamma in qmc.anneal_schedule(4, beta=2.0):
        m = pb.layered_model(beta, gamma)
        spins, _ = metropolis.run_sweeps(m, spins, "a2", 3, seed=int(gamma * 100))
        energies.append(ising.energy(m, spins))
    assert np.isfinite(energies).all()
