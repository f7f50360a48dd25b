"""Simulated quantum annealing as a served job (`SQAJob`).

An SQA job sweeps the Trotter slices of one problem at a fixed beta while
its transverse field Gamma ramps down; each Gamma step changes the slot's
tau couplings.  The contract: a served job equals its SOLO replay bit for
bit — spins, carried fields, generator columns and the slot's tau row —
wherever it lands and whatever runs beside it.  The solo replay is a
one-slot engine of its own, swept under each Gamma's layered model in
turn (`QMCProblem.layered_model`), its tables installed by a whole-table
`set_slot_model` and, on the a4 rung, the tau half of its fields rebuilt
by the plain `ising.h_eff_from_scratch`; the served path instead writes
every due slot's tau row in one `set_slot_tau` call per step.

Shapes: n=24 sites; L=32 slices on the jnp backend (V=4) and L=256 on
Pallas (interpret mode), whose 128 lanes need two layers per lane.  The
same checks on four devices are in tests/test_sqa_mesh.py.
"""

import os
import signal

import jax
import numpy as np
import pytest

from repro.core import ising, observables, qmc, reorder
from repro.core.engine import SweepEngine
from repro.runtime.ft import PreemptionHandler
from repro.serve_mc import AnnealJob, PTJob, SampleServer, SQAJob

BETA = 2.0
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


def _sqa_jobs(insts, count=5, seed0=40):
    """Ramps of 3..7 Gamma steps over the instances, in users' turn."""
    return [
        SQAJob(seed0 + k, BETA, np.linspace(3.0, 0.05, 3 + k % 5), CHUNK,
               model=insts[k % len(insts)], user=f"u{k % 2}")
        for k in range(count)
    ]


def _spec(job):
    return dict(seed=job.seed, gammas=list(job.gammas), sweeps=job._segments[0],
                problem=job.model)


def solo(spec, backend, rung, segments=None):
    """The job alone on a one-slot engine: (carry, engine, model of the
    last Gamma swept) after ``segments`` Gamma steps (all by default),
    with the boundary work of a step that is not the last done."""
    pb = qmc.QMCProblem.of(spec["problem"])
    gammas = spec["gammas"]
    K = len(gammas) if segments is None else segments
    models = [pb.layered_model(BETA, g) for g in gammas]
    V = 128 if backend == "pallas" else 4
    eng = SweepEngine.create(
        [models[0]], rung=rung, backend=backend, V=V,
        interpret=True if backend == "pallas" else None,
    )
    carry = eng.init_carry(seed=spec["seed"])
    for k in range(K):
        carry = eng.run(carry, spec["sweeps"])
        if k + 1 < len(gammas):
            m = models[k + 1]
            eng.set_slot_model(0, m)
            if rung == "a4":  # the a4 kernels read the carried fields
                flat = eng.spins_flat(carry)[0]
                _, ht = ising.h_eff_from_scratch(m, flat)
                carry = carry._replace(h_tau=jax.numpy.asarray(
                    reorder.to_lane(ht, m.n, m.L, V))[None])
    return carry, eng, models[min(K, len(gammas)) - 1]


def _assert_live_equals_solo(srv, job, backend, rung):
    """A job still in its slot: state and tau row against its solo run."""
    eng = srv.engine
    b = srv._active[job.jid][1][0]
    pb_ = int(eng.phys_slots([b])[0])
    done = job.sweeps_done // CHUNK
    ref, ref_eng, _ = solo(_spec(job), backend, rung, segments=done)
    cols = slice(pb_ * eng.V, (pb_ + 1) * eng.V)
    for name in ("spins", "h_space", "h_tau", "betas", "rng"):
        got, want = np.asarray(getattr(srv.carry, name)), np.asarray(getattr(ref, name))
        got, want = (got[:, cols], want) if name == "rng" else (got[pb_], want[0])
        np.testing.assert_array_equal(got, want,
                                      err_msg=f"{backend}/{rung} jid {job.jid}: {name}")
    # The tau row is the schedule's next value, cast to float32.
    want = np.float32(qmc.tau_coupling(BETA, job.gammas[done], job.model.L))
    for key, scale in (("tau_J", 1), ("tau_J2", 2)):
        row = np.asarray(eng.slot_tables[key][pb_])
        np.testing.assert_array_equal(row, np.full_like(row, scale * want))
        np.testing.assert_array_equal(row, np.asarray(ref_eng.slot_tables[key][0]))
    assert eng.model_of(b) is None  # rewritten tables match no model object


def _assert_result_equals_solo(r, job, backend, rung):
    ref, ref_eng, m = solo(_spec(job), backend, rung)
    flat = ref_eng.spins_flat(ref)[0]
    np.testing.assert_array_equal(np.asarray(r.spins), flat,
                                  err_msg=f"{backend}/{rung} jid {r.jid}")
    assert r.energy == observables.energies(m, flat)
    assert np.isclose(r.energy, ising.energy(m, flat), rtol=1e-12, atol=0)
    assert r.sweeps_done == CHUNK * len(job.gammas)
    pb = qmc.QMCProblem.of(job.model)
    assign = qmc.layer_majority(flat, m.n, m.L)
    np.testing.assert_array_equal(r.extras["assignment"], assign)
    assert r.extras["problem_energy"] == pb.energy(assign)
    assert r.extras["final_gamma"] == job.gammas[-1]


def served_equals_solo(backend, rung, mesh=None):
    """Serve five ramps on three slots (so slots are reused mid-ramp);
    check the jobs in their slots after three steps, then every result."""
    srv = _server(backend, rung, slots=4 if mesh is not None else 3, mesh=mesh)
    jobs = _sqa_jobs(_instances(backend))
    for j in jobs:
        srv.submit(j)
    results = []
    for _ in range(3):
        results.extend(srv.step())
    live = [j for j in jobs if j.jid in srv._active]
    assert live
    for j in live:
        _assert_live_equals_solo(srv, j, backend, rung)
    if mesh is not None:  # the rewritten tables stay sharded
        assert "data" in srv.engine.slot_tables["tau_J"].sharding.spec
    results.extend(srv.drain())
    assert sorted(r.jid for r in results) == sorted(j.jid for j in jobs)
    by_jid = {j.jid: j for j in jobs}
    for r in results:
        _assert_result_equals_solo(r, by_jid[r.jid], backend, rung)


CASES = [("jnp", "a4"), ("jnp", "cb"), ("pallas", "a4"), ("pallas", "cb")]


@pytest.mark.parametrize("backend,rung", CASES)
def test_served_sqa_job_equals_solo_replay(backend, rung):
    served_equals_solo(backend, rung)


def test_mixed_server_gives_each_job_its_solo_result():
    """SQA, anneal and PT jobs share launches: each job's result equals
    the same job served alone, and each SQA job its solo replay."""
    insts = _instances("jnp")

    def make():
        return (_sqa_jobs(insts, count=3) + [
            AnnealJob.ramp(7, 0.3, 1.5, steps=3, sweeps_per_step=CHUNK,
                           model=insts[1], user="a"),
            PTJob(8, np.array([0.4, 0.8, 1.2], np.float32), num_rounds=4,
                  sweeps_per_round=CHUNK, model=insts[2], user="p"),
            AnnealJob.constant(9, 3 * CHUNK, beta=1.1, user="a"),
        ])

    srv = _server("jnp", "cb", slots=6)
    jobs = make()
    for j in jobs:
        srv.submit(j)
    mixed = {r.jid: r for r in srv.drain()}
    assert srv.telemetry.value("engine.table_rewrites") > 0
    for k, job in enumerate(make()):
        alone = _server("jnp", "cb", slots=6)
        alone.submit(job)
        (r,) = alone.drain()
        got = mixed[jobs[k].jid]
        np.testing.assert_array_equal(np.asarray(got.spins), np.asarray(r.spins),
                                      err_msg=f"{job.kind} job {k}")
        np.testing.assert_array_equal(np.asarray(got.energy), np.asarray(r.energy))
        if isinstance(job, SQAJob):
            _assert_result_equals_solo(got, jobs[k], "jnp", "cb")
        if isinstance(job, PTJob):
            np.testing.assert_array_equal(got.extras["betas"], r.extras["betas"])


@pytest.mark.parametrize("k", [1, 7, 16])
def test_one_table_rewrite_per_step_and_one_executable(k):
    """A step with due SQA jobs makes exactly one `set_slot_tau` call,
    however many are due (k, then fewer as the shorter ramps retire),
    and every call runs the one executable compiled by the first."""
    base = ising.random_layered_model(n=6, L=8, seed=2, beta=1.0)
    srv = SampleServer(base, slots=16, multi_tenant=True, rung="cb",
                       backend="jnp", V=4, chunk_sweeps=2)
    calls = []
    orig = srv.engine.set_slot_tau

    def counted(carry, slots, rows):
        calls.append(len(slots))
        return orig(carry, slots, rows)

    srv.engine.set_slot_tau = counted
    for j in range(k):
        srv.submit(SQAJob(j, BETA, np.linspace(3.0, 0.05, 2 + j % 4), 2,
                          model=ising.reseed_couplings(base, j)))
    tel = srv.telemetry
    steps = 0
    while srv.num_active or srv.num_queued:
        before = (len(calls), tel.value("engine.table_rewrites"), tel.value("sqa.tau_rows"))
        srv.step()
        steps += 1
        # Every job sits at a Gamma boundary after each step: those still
        # active handed over their next row, those that retired none.
        ramping = srv.num_active
        assert len(calls) - before[0] == (1 if ramping else 0)
        assert tel.value("engine.table_rewrites") - before[1] == (1 if ramping else 0)
        assert tel.value("sqa.tau_rows") - before[2] == ramping
        if ramping:
            assert calls[-1] == ramping
    assert calls[0] == k and steps == max(2 + j % 4 for j in range(k))
    assert srv.engine._set_tau_jit._cache_size() == 1


def test_set_slot_model_after_a_tau_rewrite_splices_again():
    """A tau rewrite forgets the slot's model, so admitting the
    instance's own model afterwards really writes its tables back."""
    m = ising.random_layered_model(n=6, L=8, seed=5, beta=1.0)
    eng = SweepEngine.create([m, m], rung="cb", backend="jnp", V=4)
    eng.set_slot_model(0, m)
    assert eng.model_of(0) is m
    carry = eng.init_carry(seed=1)
    out = eng.set_slot_tau(carry, [0], np.full((1, m.n), 0.75, np.float32))
    assert out is carry  # cb: fields are recomputed from the tables on entry
    assert eng.model_of(0) is None and eng.model_of(1) is m
    np.testing.assert_array_equal(np.asarray(eng.slot_tables["tau_J"][0]), 0.75)
    np.testing.assert_array_equal(np.asarray(eng.slot_tables["tau_J2"][0]), 1.5)
    np.testing.assert_array_equal(np.asarray(eng.slot_tables["tau_J"][1]), m.tau_J)
    eng.set_slot_model(0, m)
    assert eng.model_of(0) is m
    for key in ("tau_J", "tau_J2"):
        np.testing.assert_array_equal(np.asarray(eng.slot_tables[key][0]),
                                      np.asarray(eng.slot_tables[key][1]))


def test_set_slot_tau_validates_and_needs_a_multi_tenant_engine():
    m = ising.random_layered_model(n=6, L=8, seed=5, beta=1.0)
    eng = SweepEngine.create([m, m], rung="a4", backend="jnp", V=4)
    carry = eng.init_carry(seed=1)
    with pytest.raises(ValueError, match="shape"):
        eng.set_slot_tau(carry, [0], np.zeros((2, m.n), np.float32))
    with pytest.raises(ValueError, match="distinct"):
        eng.set_slot_tau(carry, [1, 1], np.zeros((2, m.n), np.float32))
    with pytest.raises(ValueError, match="distinct"):
        eng.set_slot_tau(carry, [2], np.zeros((1, m.n), np.float32))
    single = SweepEngine.create(m, rung="a4", backend="jnp", batch=2, V=4)
    with pytest.raises(ValueError, match="multi-tenant"):
        single.set_slot_tau(single.init_carry(seed=1), [0],
                            np.zeros((1, m.n), np.float32))


def test_a_single_model_server_rejects_sqa_jobs():
    srv = SampleServer(BASES["jnp"], slots=2, rung="cb", backend="jnp", V=4)
    with pytest.raises(ValueError, match="multi_tenant=True"):
        srv.submit(SQAJob(1, BETA, [3.0, 1.0], CHUNK))


# -----------------------------------------------------------------------------
# Snapshots: mid-ramp, across device counts, and a SIGTERM drain.
# -----------------------------------------------------------------------------


def _results_equal(got: dict, want: dict, what: str) -> None:
    assert set(got) == set(want)
    for jid, r in got.items():
        np.testing.assert_array_equal(np.asarray(r.spins), np.asarray(want[jid].spins),
                                      err_msg=f"{what}: jid {jid}")
        assert r.energy == want[jid].energy and r.extras["problem_energy"] == \
            want[jid].extras["problem_energy"]


def snapshot_resumes(backend, rung, path, mesh=None, restore_mesh=None):
    """Snapshot mid-ramp, restore (on ``restore_mesh``), finish: equal to
    an uninterrupted D=1 run, and so is the pool's generator state."""
    insts = _instances(backend)
    ref = _server(backend, rung, slots=4)
    for j in _sqa_jobs(insts, count=6):
        ref.submit(j)
    want = {r.jid: r for r in ref.drain()}
    srv = _server(backend, rung, slots=4, mesh=mesh)
    for j in _sqa_jobs(insts, count=6):
        srv.submit(j)
    pre = []
    for _ in range(3):
        pre.extend(srv.step())
    assert srv._active and all(0 < j.sweeps_done for j, _ in srv._active.values())
    srv.snapshot(path)
    srv2 = SampleServer.restore(path, mesh=restore_mesh)
    post = srv2.drain()
    _results_equal({r.jid: r for r in pre + post}, want, f"{backend}/{rung}")
    assert list(srv2._retired) == list(ref._retired)
    np.testing.assert_array_equal(
        np.asarray(srv2.engine.extract_pool(srv2.carry).carry.rng),
        np.asarray(ref.engine.extract_pool(ref.carry).carry.rng))


@pytest.mark.parametrize("backend,rung", [("jnp", "a4"), ("jnp", "cb"), ("pallas", "cb")])
def test_snapshot_mid_ramp_resumes_bitexact(tmp_path, backend, rung):
    snapshot_resumes(backend, rung, str(tmp_path))


def test_sigterm_drain_with_sqa_jobs_queued_resumes_bitexact(tmp_path):
    insts = _instances("jnp")
    ref = _server("jnp", "cb", slots=2)
    for j in _sqa_jobs(insts, count=5):
        ref.submit(j)
    want = {r.jid: r for r in ref.drain()}

    handler = PreemptionHandler(install=True)
    try:
        srv = _server("jnp", "cb", slots=2, snapshot_manager=str(tmp_path),
                      preemption=handler)
        for j in _sqa_jobs(insts, count=5):
            srv.submit(j)
        pre = srv.step() + srv.step()
        assert srv.num_queued >= 2 and srv.num_active == 2
        if handler.installed:
            os.kill(os.getpid(), signal.SIGTERM)
        else:  # no signal hook in this thread: the flag it would set
            handler.trigger()
        assert handler.should_exit
        pre += srv.drain()  # returns at once: snapshot + preempted flag
    finally:
        handler.uninstall()
    assert srv.preempted and srv.num_queued >= 2
    srv2 = SampleServer.restore(str(tmp_path))
    assert srv2.num_queued == srv.num_queued
    post = srv2.drain()
    _results_equal({r.jid: r for r in pre + post}, want, "sigterm")
