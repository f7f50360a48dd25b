"""Anneal ramps on a multi-tenant server, and the step's one beta write.

An anneal job's hook hands its slot's next beta to the server
(`SampleServer.queue_beta`); after the hook loop the step writes every
beta handed over in one `SweepEngine.set_slot_betas` call, a jitted
scatter padded to the physical pool.  The contract: a served ramp equals
its SOLO replay bit for bit -- spins, carried fields, beta and generator
columns -- wherever it lands and whatever runs beside it.  The solo
replay is a one-slot engine of its own whose beta is replaced by hand
between segments, so it shares no code with the served write.

Shapes: n=24 sites; L=32 slices on the jnp backend (V=4) and L=256 on
Pallas (interpret mode), whose 128 lanes need two layers per lane.  The
same checks on four devices run in a subprocess that forces them.
"""

import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import repro
from repro.core import ising, observables
from repro.core.engine import SweepEngine
from repro.serve_mc import AnnealJob, PTJob, SampleServer, SQAJob

CHUNK = 4
BASES = {
    "jnp": ising.random_layered_model(n=24, L=32, seed=3, beta=1.0),
    "pallas": ising.random_layered_model(n=24, L=256, seed=3, beta=1.0),
}


def _server(backend, rung, slots=3, mesh=None, **kw):
    base = BASES[backend]
    pallas = dict(backend="pallas", V=128, interpret=True)
    return SampleServer(
        base, slots=slots, multi_tenant=True, rung=rung, chunk_sweeps=CHUNK,
        mesh=mesh, **(pallas if backend == "pallas" else dict(backend="jnp", V=4)),
        **kw,
    )


def _instances(backend, k=3):
    return [ising.reseed_couplings(BASES[backend], 100 + s) for s in range(k)]


def _ramps(insts, count=5, seed0=40):
    """Linear ramps of 3..7 betas over the instances, in users' turn."""
    return [
        AnnealJob.ramp(seed0 + k, 0.2, 1.8, steps=3 + k % 5, sweeps_per_step=CHUNK,
                       model=insts[k % len(insts)], user=f"u{k % 2}")
        for k in range(count)
    ]


def solo(job, backend, rung, segments=None):
    """The ramp alone on a one-slot engine: (carry, engine) after
    ``segments`` segments (all by default), its beta set by hand to the
    next segment's at each boundary that is not the last."""
    betas = [np.float32(b) for b in job._betas]
    K = len(betas) if segments is None else segments
    eng = SweepEngine.create(
        [job.model], rung=rung, backend=backend,
        V=128 if backend == "pallas" else 4,
        interpret=True if backend == "pallas" else None,
    )
    carry = eng.init_carry(seed=job.seed, betas=np.asarray(betas[:1]))
    for k in range(K):
        carry = eng.run(carry, job._segments[k])
        if k + 1 < len(betas):
            carry = carry._replace(betas=jnp.asarray(betas[k + 1 : k + 2]))
    return carry, eng


def _assert_live_equals_solo(srv, job, backend, rung):
    eng = srv.engine
    b = srv._active[job.jid][1][0]
    pb = int(eng.phys_slots([b])[0])
    ref, _ = solo(job, backend, rung, segments=job.sweeps_done // CHUNK)
    cols = slice(pb * eng.V, (pb + 1) * eng.V)
    for name in ("spins", "h_space", "h_tau", "betas", "rng"):
        got, want = np.asarray(getattr(srv.carry, name)), np.asarray(getattr(ref, name))
        got, want = (got[:, cols], want) if name == "rng" else (got[pb], want[0])
        np.testing.assert_array_equal(got, want,
                                      err_msg=f"{backend}/{rung} jid {job.jid}: {name}")
    assert np.asarray(srv.carry.betas)[pb] == np.float32(job.current_beta(srv))


def _assert_result_equals_solo(r, job, backend, rung):
    ref, eng = solo(job, backend, rung)
    flat = eng.spins_flat(ref)[0]
    np.testing.assert_array_equal(np.asarray(r.spins), flat,
                                  err_msg=f"{backend}/{rung} jid {r.jid}")
    assert r.energy == observables.energies(job.model, flat)
    assert r.extras["final_beta"] == np.float32(job._betas[-1])
    assert r.sweeps_done == CHUNK * len(job._betas)


def served_equals_solo(backend, rung, mesh=None):
    """Serve five ramps on three slots (so slots are reused mid-ramp);
    check the jobs in their slots after three steps, then every result."""
    srv = _server(backend, rung, slots=4 if mesh is not None else 3, mesh=mesh)
    jobs = _ramps(_instances(backend))
    for j in jobs:
        srv.submit(j)
    results = []
    for _ in range(3):
        results.extend(srv.step())
    live = [j for j in jobs if j.jid in srv._active]
    assert live
    for j in live:
        _assert_live_equals_solo(srv, j, backend, rung)
    if mesh is not None:  # the rewritten betas row stays sharded
        assert "data" in srv.carry.betas.sharding.spec
    results.extend(srv.drain())
    assert sorted(r.jid for r in results) == sorted(j.jid for j in jobs)
    by_jid = {j.jid: j for j in jobs}
    for r in results:
        _assert_result_equals_solo(r, by_jid[r.jid], backend, rung)


CASES = [("jnp", "a4"), ("jnp", "cb"), ("pallas", "a4"), ("pallas", "cb")]


@pytest.mark.parametrize("backend,rung", CASES)
def test_served_anneal_ramp_equals_solo_replay(backend, rung):
    served_equals_solo(backend, rung)


def test_mixed_server_gives_each_job_its_solo_result():
    """Anneal, SQA and PT jobs share launches: each job's result equals
    the same job served alone, and each anneal ramp its solo replay."""
    insts = _instances("jnp")

    def make():
        return _ramps(insts, count=3) + [
            SQAJob(7, 2.0, np.linspace(3.0, 0.05, 4), CHUNK, model=insts[1], user="q"),
            PTJob(8, np.array([0.4, 0.8, 1.2], np.float32), num_rounds=4,
                  sweeps_per_round=CHUNK, model=insts[2], user="p"),
            AnnealJob.constant(9, 3 * CHUNK, beta=1.1, user="a"),
        ]

    srv = _server("jnp", "cb", slots=6)
    jobs = make()
    for j in jobs:
        srv.submit(j)
    mixed = {r.jid: r for r in srv.drain()}
    tel = srv.telemetry
    assert tel.value("engine.beta_rewrites") > 0 and tel.value("engine.table_rewrites") > 0
    for k, job in enumerate(make()):
        alone = _server("jnp", "cb", slots=6)
        alone.submit(job)
        (r,) = alone.drain()
        got = mixed[jobs[k].jid]
        np.testing.assert_array_equal(np.asarray(got.spins), np.asarray(r.spins),
                                      err_msg=f"{job.kind} job {k}")
        np.testing.assert_array_equal(np.asarray(got.energy), np.asarray(r.energy))
        if k < 3:
            _assert_result_equals_solo(got, jobs[k], "jnp", "cb")
        if isinstance(job, PTJob):
            np.testing.assert_array_equal(got.extras["betas"], r.extras["betas"])


@pytest.mark.parametrize("k", [1, 7, 64])
def test_one_beta_write_per_step_and_one_executable(k):
    """A step with due anneal jobs makes exactly one `set_slot_betas`
    call, however many are due (k, then fewer as the shorter ramps
    retire), and every call runs the one executable compiled by the
    first; ``anneal.beta_rows`` counts the rows written."""
    base = ising.random_layered_model(n=6, L=8, seed=2, beta=1.0)
    srv = SampleServer(base, slots=64, multi_tenant=True, rung="cb",
                       backend="jnp", V=4, chunk_sweeps=2)
    calls = []
    orig = srv.engine.set_slot_betas

    def counted(carry, slots, betas):
        calls.append(len(slots))
        return orig(carry, slots, betas)

    srv.engine.set_slot_betas = counted
    for j in range(k):
        srv.submit(AnnealJob.ramp(j, 0.3, 1.5, steps=2 + j % 4, sweeps_per_step=2,
                                  model=ising.reseed_couplings(base, j)))
    tel = srv.telemetry
    steps = 0
    while srv.num_active or srv.num_queued:
        before = (len(calls), tel.value("engine.beta_rewrites"),
                  tel.value("anneal.beta_rows"))
        srv.step()
        steps += 1
        # Every job sits at a segment boundary after each step: those
        # still active handed over their next beta, those that retired none.
        ramping = srv.num_active
        assert len(calls) - before[0] == (1 if ramping else 0)
        assert tel.value("engine.beta_rewrites") - before[1] == (1 if ramping else 0)
        assert tel.value("anneal.beta_rows") - before[2] == ramping
        if ramping:
            assert calls[-1] == ramping
    assert calls[0] == k and tel.value("anneal.beta_rows") == sum(calls)
    assert steps == max(2 + j % 4 for j in range(k))
    assert srv.engine._set_betas_jit._cache_size() == 1


def test_set_slot_betas_writes_only_its_slots_and_validates():
    m = ising.random_layered_model(n=6, L=8, seed=5, beta=1.0)
    eng = SweepEngine.create([m] * 4, rung="a4", backend="jnp", V=4)
    carry = eng.run(eng.init_carry(seed=1), 2)
    out = eng.set_slot_betas(carry, [3, 1], [0.25, np.float64(0.7)])
    np.testing.assert_array_equal(
        np.asarray(out.betas), np.array([1.0, 0.7, 1.0, 0.25], np.float32))
    for name in ("spins", "h_space", "h_tau", "rng"):
        assert getattr(out, name) is getattr(carry, name)
    assert eng.set_slot_betas(carry, [], []) is carry
    with pytest.raises(ValueError, match="shape"):
        eng.set_slot_betas(carry, [0], [0.5, 0.6])
    with pytest.raises(ValueError, match="distinct"):
        eng.set_slot_betas(carry, [1, 1], [0.5, 0.6])
    with pytest.raises(ValueError, match="distinct"):
        eng.set_slot_betas(carry, [4], [0.5])
    with pytest.raises(ValueError, match="distinct"):
        eng.set_slot_betas(carry, [-1], [0.5])


# -----------------------------------------------------------------------------
# Snapshots mid-ramp.
# -----------------------------------------------------------------------------


def snapshot_resumes(backend, rung, path, mesh=None, restore_mesh=None):
    """Snapshot mid-ramp, restore (on ``restore_mesh``), finish: equal to
    an uninterrupted D=1 run, and so is the pool's generator state."""
    insts = _instances(backend)
    ref = _server(backend, rung, slots=4)
    for j in _ramps(insts, count=6):
        ref.submit(j)
    want = {r.jid: r for r in ref.drain()}
    srv = _server(backend, rung, slots=4, mesh=mesh)
    for j in _ramps(insts, count=6):
        srv.submit(j)
    pre = []
    for _ in range(3):
        pre.extend(srv.step())
    assert srv._active and all(0 < j.sweeps_done for j, _ in srv._active.values())
    srv.snapshot(path)
    srv2 = SampleServer.restore(path, mesh=restore_mesh)
    got = {r.jid: r for r in pre + srv2.drain()}
    assert set(got) == set(want)
    for jid, r in got.items():
        np.testing.assert_array_equal(np.asarray(r.spins), np.asarray(want[jid].spins),
                                      err_msg=f"{backend}/{rung}: jid {jid}")
        assert r.energy == want[jid].energy
        assert r.extras["final_beta"] == want[jid].extras["final_beta"]
    assert list(srv2._retired) == list(ref._retired)
    np.testing.assert_array_equal(
        np.asarray(srv2.engine.extract_pool(srv2.carry).carry.rng),
        np.asarray(ref.engine.extract_pool(ref.carry).carry.rng))


@pytest.mark.parametrize("rung", ["a4", "cb"])
def test_snapshot_mid_ramp_resumes_bitexact(tmp_path, rung):
    snapshot_resumes("jnp", rung, str(tmp_path))


# -----------------------------------------------------------------------------
# Four forced devices, in a subprocess.
# -----------------------------------------------------------------------------


def mesh_write_equals_unsharded(mesh, capacities=None):
    """The jitted write on a mesh keeps the betas row sharded over
    ``data`` and equals the same write on one device, for slots on
    every device (and, with ``capacities``, a ragged pool)."""
    base = ising.random_layered_model(n=6, L=8, seed=5, beta=1.0)
    models = [ising.reseed_couplings(base, s) for s in range(8)]
    ref = SweepEngine.create(models, rung="cb", backend="jnp", V=4)
    sh = SweepEngine.create(models, rung="cb", backend="jnp", V=4, mesh=mesh,
                            capacities=capacities)
    slots, betas = [7, 0, 5, 2], [0.25, 1.5, 0.75, 2.0]
    want = ref.set_slot_betas(ref.init_carry(seed=3), slots, betas)
    got = sh.set_slot_betas(sh.init_carry(seed=3), slots, betas)
    assert got.betas.sharding.spec == P("data")
    np.testing.assert_array_equal(np.asarray(sh.extract_pool(got).carry.betas),
                                  np.asarray(want.betas))


_SRC = os.path.abspath(os.path.join(list(repro.__path__)[0], ".."))
_HERE = os.path.dirname(os.path.abspath(__file__))


def _four_devices(code: str) -> None:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([_SRC, _HERE, os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-6000:]
    assert "four-devices-ok" in out.stdout


def test_on_four_devices_the_write_stays_sharded_and_ramps_equal_solo():
    _four_devices(
        "import jax\n"
        "assert len(jax.devices()) == 4\n"
        "from repro.launch.mesh import make_slot_mesh\n"
        "import test_anneal_betas as t\n"
        "mesh = make_slot_mesh(4)\n"
        "t.mesh_write_equals_unsharded(mesh)\n"
        "t.mesh_write_equals_unsharded(mesh, capacities=[4, 2, 1, 1])\n"
        "for rung in ('a4', 'cb'):\n"
        "    t.served_equals_solo('jnp', rung, mesh=mesh)\n"
        "print('four-devices-ok')\n"
    )
