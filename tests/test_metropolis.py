"""Metropolis ladder: bit-exact rung equivalences, invariants, kernel oracle."""

import hypothesis.strategies as st
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings

from repro.core import ising, metropolis, mt19937, reorder
from repro.kernels import ops, ref


@pytest.fixture(scope="module")
def model():
    return ising.random_layered_model(n=6, L=8, seed=3, beta=0.7)


def test_a1_equals_a2_bit_exact(model):
    """Same exp flavour + same RNG stream -> the data-structure change
    (Fig 4 -> Fig 5/6) must not change a single bit."""
    s0 = ising.init_spins(model, 1)
    s1, _ = metropolis.run_sweeps(model, s0, "a1", 3, seed=99, exp_flavor="fast")
    s2, _ = metropolis.run_sweeps(model, s0, "a2", 3, seed=99, exp_flavor="fast")
    np.testing.assert_array_equal(s1, s2)


def test_a3_equals_a4(model):
    s0 = ising.init_spins(model, 2)
    s3, _ = metropolis.run_sweeps(model, s0, "a3", 2, seed=5, V=4)
    s4, _ = metropolis.run_sweeps(model, s0, "a4", 2, seed=5, V=4)
    np.testing.assert_array_equal(s3, s4)


def _vector_vs_reference(m, V, seed):
    """A.4 lane sweep == sequential reference over the relabeled model."""
    rows = reorder.check_lane_shape(m.n, m.L, V)
    spins0 = ising.init_spins(m, seed)
    rng = mt19937.mt_init(np.arange(V, dtype=np.uint32) * 2654435761 + 1234)
    rng, u = mt19937.mt_uniform_blocks(rng, -(-rows // mt19937.N))
    u = u[:rows]

    lane = metropolis.make_lane_state(m, spins0, V)
    lane = metropolis.sweep_lane(
        lane, jnp.asarray(m.space_nbr), jnp.asarray(2.0 * m.space_J),
        jnp.asarray(2.0 * m.tau_J), jnp.asarray(u), m.beta, m.n, "fast",
    )
    tgt, J2 = reorder.relabeled_flat_arrays(m, V)
    perm = reorder.flat_to_lane_perm(m.n, m.L, V)
    hs0, ht0 = ising.h_eff_from_scratch(m, spins0)
    flat = metropolis.FlatState(
        jnp.asarray(spins0[perm]), jnp.asarray(hs0[perm]), jnp.asarray(ht0[perm])
    )
    flat = metropolis.sweep_flat(
        flat, jnp.asarray(tgt), jnp.asarray(J2), jnp.asarray(u.reshape(-1)),
        m.beta, m.space_degree, "fast",
    )
    np.testing.assert_array_equal(
        np.asarray(lane.spins).reshape(-1), np.asarray(flat.spins)
    )
    np.testing.assert_array_equal(
        np.asarray(lane.h_space).reshape(-1), np.asarray(flat.h_space)
    )
    np.testing.assert_array_equal(
        np.asarray(lane.h_tau).reshape(-1), np.asarray(flat.h_tau)
    )


@pytest.mark.parametrize("V", [2, 4])
def test_vectorized_equals_sequential_oracle(model, V):
    _vector_vs_reference(model, V, seed=7)


@given(
    n=st.integers(3, 8),
    lpv=st.integers(2, 4),
    V=st.sampled_from([2, 4]),
    seed=st.integers(0, 10_000),
)
@settings(max_examples=8)
def test_vectorized_equals_oracle_property(n, lpv, V, seed):
    m = ising.random_layered_model(n=n, L=lpv * V, seed=seed % 97, beta=0.9)
    _vector_vs_reference(m, V, seed)


def test_h_eff_invariant_after_sweeps(model):
    """Incrementally-maintained fields == recomputed-from-scratch fields."""
    s0 = ising.init_spins(model, 4)
    sfin, state = metropolis.run_sweeps(model, s0, "a2", 5, seed=11)
    hs, ht = ising.h_eff_from_scratch(model, sfin)
    np.testing.assert_allclose(np.asarray(state.h_space), hs, atol=2e-4)
    np.testing.assert_allclose(np.asarray(state.h_tau), ht, atol=2e-4)


def test_energy_decreases_at_low_temperature():
    m = ising.random_layered_model(n=8, L=8, seed=11, beta=3.0)
    s0 = ising.init_spins(m, 2)
    e0 = ising.energy(m, s0)
    sf, _ = metropolis.run_sweeps(m, s0, "a2", 30, seed=7)
    assert ising.energy(m, sf) < e0


def test_boltzmann_distribution_two_spin():
    """Detailed balance check: empirical state distribution of a 2-spin
    system matches Boltzmann within statistical tolerance."""
    # 1 layer pair (L=2 gives tau bonds), 1 spin per layer -> 2 coupled spins.
    m = ising.LayeredModel(
        n=1, L=2, h=np.array([0.3], np.float32),
        space_nbr=np.zeros((1, 1), np.int32), space_J=np.zeros((1, 1), np.float32),
        tau_J=np.array([0.5], np.float32), beta=1.0,
    )
    s = ising.init_spins(m, 0)
    counts = {}
    state = None
    # NOTE: L=2 means both tau edges connect the same pair; energy uses
    # J_tau twice (wraparound), which ising.energy accounts for.
    from repro.core.metropolis import run_sweeps

    spins = s
    for i in range(600):
        spins, _ = run_sweeps(m, spins, "a2", 1, seed=1000 + i, exp_flavor="exact")
        key = tuple(int(x) for x in spins)
        counts[key] = counts.get(key, 0) + 1
    states = sorted(counts)
    e = {st_: ising.energy(m, np.array(st_, np.float32)) for st_ in states}
    z = sum(np.exp(-m.beta * ev) for ev in e.values())
    for st_ in states:
        expected = np.exp(-m.beta * e[st_]) / z
        observed = counts[st_] / 600
        assert abs(observed - expected) < 0.12, (st_, observed, expected)


def test_pallas_kernel_matches_a4_oracle():
    m = ising.random_layered_model(n=6, L=256, seed=5, beta=1.1)
    inputs = ops.make_kernel_inputs(m, batch=2, seed=9)
    out_k = ops.metropolis_sweep(*inputs, n=m.n)
    out_r = ref.metropolis_sweep_ref(*inputs, n=m.n)
    for a, b in zip(out_k, out_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pallas_kernel_h_eff_invariant():
    m = ising.random_layered_model(n=5, L=256, seed=6, beta=0.8)
    inputs = ops.make_kernel_inputs(m, batch=1, seed=3)
    spins, hs, ht = ops.metropolis_sweep(*inputs, n=m.n)
    flat = reorder.from_lane(np.asarray(spins[0]), m.n, m.L, 128)
    hs_ref, ht_ref = ising.h_eff_from_scratch(m, flat)
    np.testing.assert_allclose(
        reorder.from_lane(np.asarray(hs[0]), m.n, m.L, 128), hs_ref, atol=2e-4
    )
    np.testing.assert_allclose(
        reorder.from_lane(np.asarray(ht[0]), m.n, m.L, 128), ht_ref, atol=2e-4
    )


KERNEL_ENTRIES = [
    "metropolis_kernel.metropolis_sweep_kernel",
    "metropolis_kernel.metropolis_multisweep_kernel",
    "metropolis_kernel.metropolis_multisweep_multi_kernel",
    "metropolis_kernel.make_colored_multisweep_kernel",
    "metropolis_kernel.make_colored_multisweep_multi_kernel",
    "mt19937_kernel.mt_next_block_kernel",
    "mt19937_kernel.mt_uniforms_kernel",
    "mt19937_kernel.mt_uniform_blocks_kernel",
    "fastexp_kernel.fastexp_2d",
]


@pytest.mark.parametrize("entry", KERNEL_ENTRIES)
def test_kernel_entry_has_no_interpret_default(entry):
    """Only `ops._auto_interpret` chooses interpret mode: a caller that
    goes around `ops` has to say which mode it wants."""
    import importlib
    import inspect

    module, name = entry.split(".")
    fn = getattr(importlib.import_module(f"repro.kernels.{module}"), name)
    param = inspect.signature(fn).parameters["interpret"]
    assert param.kind is inspect.Parameter.KEYWORD_ONLY
    assert param.default is inspect.Parameter.empty


def test_auto_interpret_is_compiled_on_tpu_unless_asked(monkeypatch):
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    assert ops._auto_interpret(None) is False
    assert ops._auto_interpret(True) is True
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "cpu")
    assert ops._auto_interpret(None) is True
    assert ops._auto_interpret(False) is False


def test_reorder_roundtrip():
    m = ising.random_layered_model(n=4, L=8, seed=0)
    x = np.arange(m.num_spins, dtype=np.int64)
    back = reorder.from_lane(reorder.to_lane(x, m.n, m.L, 4), m.n, m.L, 4)
    np.testing.assert_array_equal(back, x)


def test_reorder_rejects_bad_shapes():
    with pytest.raises(ValueError):
        reorder.check_lane_shape(4, 6, 4)  # L not divisible by V
    with pytest.raises(ValueError):
        reorder.check_lane_shape(4, 4, 4)  # L//V < 2 (tau-adjacent lanes)
