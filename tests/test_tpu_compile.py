"""The main path's kernels compile for a TPU v5e, at the paper's shape.

No chip is attached here: the TPU compiler compiles for a described
``v5e:2x2`` topology, so these tests catch what interpret mode cannot (a
refused primitive or layout, a tile that overflows VMEM) at no chip time.
The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and it
keeps it until it exits.

Shape: the AQUA@Home deployment of `configs/ising_qmc.py` (96 spins x 256
layers, rows=192 at V=128), 32 replica slots, and the replica tile the
kernels pick on their own (`metropolis_kernel.pick_replica_tile`).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs.ising_qmc import CONFIG
from repro.core import ising, mt19937 as mt, reorder
from repro.core.engine import SweepCarry, SweepEngine
from repro.kernels import metropolis_kernel as mk

N, L, LANES, SLOTS, SWEEPS = CONFIG.spins_per_layer, CONFIG.num_layers, 128, 32, 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def model():
    return ising.random_layered_model(n=N, L=L, seed=CONFIG.seed)


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_paper_shape_replica_tile_fits_vmem(model):
    rows = reorder.check_lane_shape(N, L, LANES)
    for fields_in, multi in ((True, False), (True, True), (False, False), (False, True)):
        per = mk.replica_vmem_bytes(rows, N, model.space_degree + 2,
                                    fields_in=fields_in, multi=multi)
        bt = mk.pick_replica_tile(SLOTS, per)
        assert SLOTS % bt == 0 and 1 < bt < SLOTS
        assert bt * per <= mk.TILE_VMEM_BUDGET


@pytest.mark.parametrize("multi", [False, True], ids=["single", "multi"])
def test_a4_kernel_compiles_for_v5e(one_chip, model, multi):
    rows, sd = reorder.check_lane_shape(N, L, LANES), model.space_degree
    S = lambda shape, dt=jnp.float32: _shape(one_chip, shape, dt)
    field = S((SLOTS, rows, LANES))
    args = [field, field, field, S((mt.N, SLOTS * LANES), jnp.uint32),
            S((N, sd), jnp.int32)]
    if multi:
        entry = mk.metropolis_multisweep_multi_kernel
        args += [S((SLOTS, N, sd)), S((SLOTS, N))]
    else:
        entry = mk.metropolis_multisweep_kernel
        args += [S((N, sd)), S((N,))]
    args.append(S((SLOTS,)))
    text = _compiled_text(
        lambda *a: entry(*a, n=N, num_sweeps=SWEEPS, interpret=False), *args
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("multi", [False, True], ids=["single", "multi"])
def test_cb_kernel_compiles_for_v5e(one_chip, model, multi):
    rows, sd = reorder.check_lane_shape(N, L, LANES), model.space_degree
    S = lambda shape, dt=jnp.float32: _shape(one_chip, shape, dt)
    classes = reorder.colored_classes(model, LANES)
    field, gen, beta = (S((SLOTS, rows, LANES)),
                        S((mt.N, SLOTS * LANES), jnp.uint32), S((SLOTS,)))
    if multi:
        fn = mk.make_colored_multisweep_multi_kernel(
            classes, model.space_nbr, N, interpret=False)
        text = _compiled_text(
            lambda s, r, b, h, j, t: fn(s, r, b, h, j, t, SWEEPS),
            field, gen, beta, S((SLOTS, N)), S((SLOTS, N, sd)), S((SLOTS, N)),
        )
    else:
        fn = mk.make_colored_multisweep_kernel(
            classes, model.h, model.space_nbr, model.space_J, model.tau_J, N,
            interpret=False)
        text = _compiled_text(lambda s, r, b: fn(s, r, b, SWEEPS), field, gen, beta)
    assert "tpu_custom_call" in text


def _carry_shapes(eng, shardings):
    rows = reorder.check_lane_shape(N, L, LANES)
    B = eng.batch
    return SweepCarry(
        _shape(shardings.spins, (B, rows, LANES)),
        _shape(shardings.h_space, (B, rows, LANES)),
        _shape(shardings.h_tau, (B, rows, LANES)),
        _shape(shardings.betas, (B,)),
        _shape(shardings.rng, (mt.N, B * LANES), jnp.uint32),
    )


def test_cb_jnp_engine_step_compiles_for_v5e(one_chip, model):
    eng = SweepEngine.create(model, rung="cb", backend="jnp", batch=SLOTS, V=LANES)
    carry = _carry_shapes(eng, SweepCarry(*[one_chip] * 5))
    eng._run_jit.lower(carry, SWEEPS).compile()


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_cb_engine_step_sharded_over_four_chips_compiles(topo, model, backend):
    mesh = Mesh(np.asarray(topo.devices), ("data",))
    # The default backend here is the CPU, so the compiled kernel is asked
    # for by name (on a TPU, ``interpret=None`` picks it).
    kw = dict(interpret=False) if backend == "pallas" else {}
    eng = SweepEngine.create(model, rung="cb", backend=backend, batch=SLOTS,
                             V=LANES, mesh=mesh, **kw)
    shardings = SweepCarry(*(NamedSharding(mesh, s) for s in eng._carry_pspecs()))
    compiled = eng._run_jit.lower(_carry_shapes(eng, shardings), SWEEPS).compile()
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == (backend == "pallas")
    # The sweep is slot-parallel: no collective on the hot path.
    for op in ("all-gather", "all-reduce", "collective-permute", "all-to-all"):
        assert op not in text, op
    assert compiled.output_shardings[0].spec == P("data", None, None)


def test_pt_swap_slots_compiles_for_v5e(one_chip, model):
    """The served PT swap phase: one call over the whole carry's spins and
    betas for a ladder in half the slots."""
    from repro.core import tempering

    eng = SweepEngine.create(model, rung="cb", backend="jnp", batch=SLOTS, V=LANES)
    rows, R = eng.tables["rows"], SLOTS // 2
    tables = [_shape(one_chip, t.shape, t.dtype)
              for t in tempering.energy_tables(eng)]
    scalar = _shape(one_chip, (), jnp.int32)
    compiled = tempering.swap_slots.lower(
        _shape(one_chip, (SLOTS, rows, LANES)), _shape(one_chip, (SLOTS,)),
        _shape(one_chip, (R,), jnp.int32), _shape(one_chip, (mt.N,), jnp.uint32),
        scalar, scalar, scalar, *tables, n=N, exp_flavor=eng.exp_flavor,
    ).compile()
    betas, swap_rng, acc, prop = compiled.out_info
    assert betas.shape == (SLOTS,) and swap_rng.shape == (mt.N,)
    assert acc.shape == prop.shape == ()


@pytest.mark.parametrize("rung", ["a4", "cb"])
def test_slot_tau_rewrite_compiles_for_v5e(one_chip, model, rung):
    """The one table-rewrite call of a step (`set_slot_tau`), padded to the
    whole pool; on the a4 rung it also rebuilds the slots' tau fields."""
    eng = SweepEngine.create([model] * SLOTS, rung=rung, backend="jnp", V=LANES)
    fn = eng._build_set_tau()
    tabs = {k: _shape(one_chip, v.shape, v.dtype) for k, v in eng.slot_tables.items()}
    idx, rows = _shape(one_chip, (SLOTS,), jnp.int32), _shape(one_chip, (SLOTS, N))
    if rung == "a4":
        carry = _carry_shapes(eng, SweepCarry(*[one_chip] * 5))
        tabs_out, carry_out = fn.lower(tabs, carry, idx, rows).compile().out_info
        assert carry_out.h_tau.shape == carry.h_tau.shape
    else:
        tabs_out = fn.lower(tabs, idx, rows).compile().out_info
    assert tabs_out["tau_J"].shape == tabs_out["tau_J2"].shape == (SLOTS, N)


def test_slot_beta_write_compiles_for_v5e(one_chip, model):
    """The one beta write of a step (`set_slot_betas`), padded to the
    anneal backlog's 256-slot multi-tenant pool, cb rung."""
    backlog = 256
    eng = SweepEngine.create([model] * backlog, rung="cb", backend="jnp", V=LANES)
    row = _shape(one_chip, (backlog,))
    idx = _shape(one_chip, (backlog,), jnp.int32)
    out = eng._build_set_betas().lower(row, idx, row).compile().out_info
    assert out.shape == (backlog,) and out.dtype == jnp.float32
