"""SampleServer: scheduler determinism, anneal schedules, PT-as-a-job.

The load-bearing guarantee (DESIGN.md §Service): a job's final spins,
energy, and RNG state are bit-identical whether it ran solo (slots=1) or
packed with arbitrary neighbours, across admit/retire slot reuse and
regardless of chunk size — because slots own private RNG lane columns and
chunks never cross segment boundaries.
"""

import numpy as np
import pytest

from repro.core import engine, ising, observables, reorder, tempering
from repro.serve_mc import AnnealJob, PTJob, SampleServer

MODEL = ising.random_layered_model(n=5, L=8, seed=1, beta=1.0)
MIXED = [(10, 3), (11, 7), (12, 5), (13, 4), (14, 9)]  # (seed, budget)


def _server(m=MODEL, **kw):
    kw.setdefault("rung", "a4")
    kw.setdefault("backend", "jnp")
    kw.setdefault("V", 4)
    return SampleServer(m, **kw)


# -----------------------------------------------------------------------------
# Scheduler determinism: solo == packed, bit for bit.
# -----------------------------------------------------------------------------


@pytest.mark.parametrize("slots,chunk", [(2, 4), (3, 2), (5, 8)])
def test_solo_equals_packed_across_slot_reuse(slots, chunk):
    """5 mixed-budget jobs through a small server: retire/admit reuses
    slots mid-flight, chunk sizes differ between the two runs, results
    must not change by a single bit."""
    packed = _server(slots=slots, chunk_sweeps=chunk)
    jobs = [AnnealJob.constant(seed=s, sweeps=b, beta=1.0) for s, b in MIXED]
    for j in jobs:
        packed.submit(j)
    by_jid = {r.jid: r for r in packed.drain()}
    assert sorted(by_jid) == [j.jid for j in jobs]
    for (s, b), job in zip(MIXED, jobs):
        solo = _server(slots=1, chunk_sweeps=5)  # different chunking on purpose
        solo.submit(AnnealJob.constant(seed=s, sweeps=b, beta=1.0))
        (r_solo,) = solo.drain()
        r_packed = by_jid[job.jid]
        np.testing.assert_array_equal(r_solo.spins, r_packed.spins)
        assert r_solo.energy == r_packed.energy
        assert r_solo.sweeps_done == r_packed.sweeps_done == b


def test_served_job_equals_raw_engine_run():
    """A constant-beta job is exactly a solo SweepEngine run of the same
    seed/budget (the server adds scheduling, not physics)."""
    srv = _server(slots=2, chunk_sweeps=3)
    srv.submit(AnnealJob.constant(seed=11, sweeps=7))  # beta=None -> model beta
    srv.submit(AnnealJob.constant(seed=23, sweeps=4, beta=0.7))
    res = {r.jid: r for r in srv.drain()}
    eng = engine.SweepEngine.build(MODEL, rung="a4", backend="jnp", batch=1, V=4)
    carry = eng.run(eng.init_carry(seed=11), 7)
    np.testing.assert_array_equal(res[0].spins, eng.spins_flat(carry)[0])
    assert res[0].energy == ising.energy(MODEL, eng.spins_flat(carry)[0])


def test_rng_stream_independent_of_neighbours():
    """The retired slot's RNG columns equal the solo run's generator state
    — per-slot streams advance the same regardless of batch packing."""
    packed = _server(slots=3, chunk_sweeps=2)
    job = AnnealJob.constant(seed=7, sweeps=4, beta=1.1)
    packed.submit(job)
    packed.submit(AnnealJob.constant(seed=8, sweeps=6, beta=0.5))
    packed.step()  # job still active after 2 of 4 sweeps
    sub = packed.engine.extract_slot(packed.carry, 0)
    solo = _server(slots=1, chunk_sweeps=2)
    solo.submit(AnnealJob.constant(seed=7, sweeps=4, beta=1.1))
    solo.step()
    np.testing.assert_array_equal(np.asarray(sub.rng), np.asarray(solo.carry.rng))


# -----------------------------------------------------------------------------
# Anneal schedules.
# -----------------------------------------------------------------------------


def test_anneal_schedule_rewrites_betas_between_chunks():
    """A two-segment schedule equals a manual run that rewrites betas at
    the segment boundary — even when chunks subdivide the segments."""
    sched = [(5, 0.3), (4, 1.5)]
    srv = _server(slots=2, chunk_sweeps=2)  # 5 = 2+2+1: misaligned chunks
    srv.submit(AnnealJob(seed=4, schedule=sched))
    srv.submit(AnnealJob.constant(seed=41, sweeps=3, beta=1.0))  # neighbour
    res = {r.jid: r for r in srv.drain()}
    eng = engine.SweepEngine.build(MODEL, rung="a4", backend="jnp", batch=1, V=4)
    carry = eng.init_carry(seed=4, betas=np.array([0.3], np.float32))
    carry = eng.run(carry, 5)
    carry = carry._replace(betas=np.array([1.5], np.float32))
    carry = eng.run(carry, 4)
    np.testing.assert_array_equal(res[0].spins, eng.spins_flat(carry)[0])
    assert res[0].extras["final_beta"] == np.float32(1.5)
    assert res[0].sweeps_done == 9


def test_ramp_constructor():
    job = AnnealJob.ramp(seed=0, beta_start=0.2, beta_end=1.0, steps=5,
                         sweeps_per_step=2)
    assert job.total_remaining() == 10
    assert [round(b, 2) for b in job._betas] == [0.2, 0.4, 0.6, 0.8, 1.0]


# -----------------------------------------------------------------------------
# Parallel tempering as a multi-slot job.
# -----------------------------------------------------------------------------


@pytest.mark.parametrize("tenancy", ["single", "private"])
def test_pt_job_equals_standalone_driver(tenancy):
    """A PTJob packed beside an anneal job whose segments do NOT align
    with PT rounds (rounds get split across chunks) must still reproduce
    tempering.run_parallel_tempering bit for bit -- on a single-model
    server, and on a multi-tenant server whose base model is another
    instance while the ladder carries its own."""
    m = ising.random_layered_model(n=4, L=8, seed=2, beta=1.0)
    betas = np.linspace(0.4, 1.4, 4).astype(np.float32)
    rounds, spr = 3, 2
    state, energies = tempering.run_parallel_tempering(
        m, betas, rounds, V=4, seed=5, sweeps_per_round=spr, backend="jnp"
    )
    solo_spins = np.stack(
        [reorder.from_lane(np.asarray(s), m.n, m.L, 4) for s in state.spins]
    )
    if tenancy == "single":
        srv = SampleServer(m, slots=6, chunk_sweeps=4, rung="a4",
                           backend="jnp", V=4)
        pt = PTJob(seed=5, betas=betas, num_rounds=rounds, sweeps_per_round=spr)
    else:
        base = ising.reseed_couplings(m, seed=41, beta=0.9)
        srv = SampleServer(base, slots=6, chunk_sweeps=4, rung="a4",
                           backend="jnp", V=4, multi_tenant=True)
        pt = PTJob(seed=5, betas=betas, num_rounds=rounds, sweeps_per_round=spr,
                   model=m)
    # Budget 5 forces chunk sizes 2,2,1,... -> PT rounds split mid-round.
    srv.submit(AnnealJob.constant(seed=99, sweeps=5, beta=0.8))
    srv.submit(pt)
    res = {r.jid: r for r in srv.drain()}
    r = res[pt.jid]
    np.testing.assert_array_equal(r.spins, solo_spins)
    np.testing.assert_array_equal(r.extras["betas"], np.asarray(state.betas))
    np.testing.assert_allclose(r.energy, energies, rtol=1e-5)
    assert r.extras["swap_propose"] == int(state.swap_propose)
    assert r.extras["swap_accept"] == int(state.swap_accept)
    np.testing.assert_array_equal(np.asarray(pt.swap_rng),
                                  np.asarray(state.swap_rng))


def test_pt_swaps_share_one_executable_across_slots():
    """Two ladders of one size on different slots, through rounds of both
    parities: every swap phase is one `tempering.swap_slots` call and all
    of them run one executable; each is counted as a local swap on an
    unsharded server."""
    m = ising.random_layered_model(n=4, L=8, seed=6, beta=1.0)
    srv = SampleServer(m, slots=7, chunk_sweeps=2, rung="a4", backend="jnp",
                       V=4)
    betas = np.linspace(0.5, 1.5, 3).astype(np.float32)
    jobs = [PTJob(seed=s, betas=betas, num_rounds=4, sweeps_per_round=2)
            for s in (3, 4)]
    for job in jobs:
        srv.submit(job)
    tempering.swap_slots.clear_cache()
    srv.drain()
    assert tempering.swap_slots._cache_size() == 1
    assert [job._rows[0] for job in jobs] == [(0, 1, 2), (3, 4, 5)]
    place = srv.stats()["placement"]
    assert place["pt_swap_local"] == 8 and place["pt_swap_cross"] == 0


def test_pt_job_waits_for_enough_free_slots():
    """FIFO admission: a 3-slot PT job queues until 3 slots free up."""
    m = ising.random_layered_model(n=4, L=8, seed=3, beta=1.0)
    srv = SampleServer(m, slots=3, chunk_sweeps=2, rung="a4", backend="jnp", V=4)
    srv.submit(AnnealJob.constant(seed=1, sweeps=2, beta=1.0))
    pt = PTJob(seed=9, betas=np.array([0.5, 1.0, 1.5], np.float32), num_rounds=2)
    srv.submit(pt)
    srv.step()  # anneal job runs alone; PT blocked (needs 3 slots, 2 free)
    assert srv.num_active == 0 or pt.jid not in srv._active
    results = srv.drain()
    assert {r.jid for r in results} >= {pt.jid}


# -----------------------------------------------------------------------------
# Multi-tenant stress: a seeded random admit/retire/chunk schedule over
# mixed Anneal/PT jobs with DIFFERENT models must reproduce every job's
# solo run bit for bit (generalizes the fixed-schedule tests above).
# -----------------------------------------------------------------------------


VARIANTS = [
    None,  # the server's base model
    ising.reseed_couplings(MODEL, seed=31, beta=0.9),
    ising.reseed_couplings(MODEL, seed=32, beta=1.1),
]


def _random_job_specs(rng, num_jobs):
    specs = []
    for i in range(num_jobs):
        mi = int(rng.integers(0, len(VARIANTS)))
        if i % 4 == 2:
            specs.append(
                ("pt", 300 + i, mi, int(rng.integers(1, 4)), 2)
            )  # (kind, seed, model idx, rounds, sweeps/round)
        else:
            specs.append(
                ("anneal", 300 + i, mi, int(rng.integers(2, 11)),
                 float(rng.uniform(0.5, 1.5)))
            )  # (kind, seed, model idx, budget, beta)
    return specs


def _make_job(spec):
    kind, seed, mi, a, b = spec
    model = VARIANTS[mi]
    if kind == "pt":
        betas = np.linspace(0.5, 1.3, 2).astype(np.float32)
        return PTJob(seed=seed, betas=betas, num_rounds=a, sweeps_per_round=b,
                     model=model)
    return AnnealJob.constant(seed=seed, sweeps=a, beta=b, model=model)


@pytest.mark.parametrize("rung", ["a4", "cb"])
def test_random_slot_reuse_multi_model_stress(rung):
    rng = np.random.default_rng(2024)
    specs = _random_job_specs(rng, num_jobs=9)
    jobs = [_make_job(s) for s in specs]
    packed = SampleServer(
        MODEL, slots=4, chunk_sweeps=3, rung=rung, backend="jnp", V=4,
        multi_tenant=True,
    )
    # Random admission times: jobs arrive while earlier ones are mid-
    # flight, so slots are retired and re-spliced (carry AND tables) with
    # different tenants in arbitrary order.
    results, pending = [], list(jobs)
    while pending or packed.num_active or packed.num_queued:
        if pending and rng.random() < 0.6:
            packed.submit(pending.pop(0))
        if packed.num_active or packed.num_queued:
            results.extend(packed.step())
    by_jid = {r.jid: r for r in results}
    assert sorted(by_jid) == sorted(j.jid for j in jobs)

    for spec, job in zip(specs, jobs):
        kind, seed, mi, a, b = spec
        model = VARIANTS[mi] or MODEL
        got = by_jid[job.jid]
        if kind == "pt":
            state, energies = tempering.run_parallel_tempering(
                model, np.linspace(0.5, 1.3, 2).astype(np.float32), a,
                V=4, seed=seed, sweeps_per_round=b, rung=rung, backend="jnp",
            )
            want = np.stack(
                [reorder.from_lane(np.asarray(s), model.n, model.L, 4)
                 for s in state.spins]
            )
            np.testing.assert_array_equal(got.spins, want)
            np.testing.assert_array_equal(
                got.extras["betas"], np.asarray(state.betas)
            )
            assert got.extras["swap_propose"] == int(state.swap_propose)
        else:
            solo = SampleServer(
                MODEL, slots=1, chunk_sweeps=5, rung=rung, backend="jnp",
                V=4, multi_tenant=True,
            )  # different chunking on purpose
            solo.submit(_make_job(spec))
            (r_solo,) = solo.drain()
            np.testing.assert_array_equal(r_solo.spins, got.spins)
            assert r_solo.energy == got.energy


def test_multi_tenant_homogeneous_bit_equals_single_model_server():
    """A model-less job mix through a multi_tenant server equals the same
    mix through today's single-model server, bit for bit — the multi path
    is a strict superset, not a fork."""
    def run(multi):
        srv = _server(slots=3, chunk_sweeps=2, multi_tenant=multi)
        for s, b in MIXED:
            srv.submit(AnnealJob.constant(seed=s, sweeps=b, beta=1.0))
        return srv.drain()

    for r1, rm in zip(run(False), run(True)):
        np.testing.assert_array_equal(r1.spins, rm.spins)
        assert r1.energy == rm.energy


def test_multi_tenant_submit_validation():
    variant = ising.reseed_couplings(MODEL, seed=5)
    srv = _server(slots=2, chunk_sweeps=2)  # single-model server
    with pytest.raises(ValueError, match="multi_tenant"):
        srv.submit(AnnealJob.constant(seed=0, sweeps=1, model=variant))
    srv_m = _server(slots=2, chunk_sweeps=2, multi_tenant=True)
    other = ising.random_layered_model(n=5, L=8, seed=77, beta=1.0)
    with pytest.raises(ValueError, match="topology"):
        srv_m.submit(AnnealJob.constant(seed=0, sweeps=1, model=other))


# -----------------------------------------------------------------------------
# Backend parity: the scheduler is backend-agnostic.
# -----------------------------------------------------------------------------


def test_serve_pallas_equals_jnp():
    """Same job set on a pallas(interpret) server and a jnp server:
    bit-identical results (the engine's backend parity survives the
    scheduler's splice/extract path)."""
    m = ising.random_layered_model(n=2, L=256, seed=4, beta=1.0)
    specs = [(5, 3, 1.0), (6, 5, 0.8)]

    def run(backend):
        srv = SampleServer(m, slots=2, chunk_sweeps=2, backend=backend,
                           V=128, interpret=True if backend == "pallas" else None)
        for s, b, beta in specs:
            srv.submit(AnnealJob.constant(seed=s, sweeps=b, beta=beta))
        return srv.drain()

    for rj, rp in zip(run("jnp"), run("pallas")):
        np.testing.assert_array_equal(rj.spins, rp.spins)
        assert rj.energy == rp.energy


# -----------------------------------------------------------------------------
# Adaptive chunk sizing.
# -----------------------------------------------------------------------------


def test_adaptive_chunks_bounded_jit_cache():
    """chunk_sweeps="adaptive" picks every launch's chunk from the fixed
    power-of-two menu — even when segment boundaries clamp it — so the
    number of distinct compiled run executables is bounded by the menu
    size regardless of job budgets or queue depth."""
    srv = _server(slots=2, chunk_sweeps="adaptive")
    # Awkward budgets/segments that a naive min(chunk, remaining) would
    # turn into arbitrary chunk sizes (5, 3, 7, ...).
    budgets = [5, 7, 13, 9, 3, 11, 6]
    for i, b in enumerate(budgets):
        srv.submit(AnnealJob.constant(seed=100 + i, sweeps=b, beta=1.0))
    srv.submit(AnnealJob(seed=50, schedule=[(5, 0.4), (7, 0.9), (3, 1.4)]))
    results = srv.drain()
    assert len(results) == len(budgets) + 1
    menu = set(srv._chunker.menu)
    assert set(srv.launch_chunks) <= menu
    assert srv.stats()["distinct_chunks"] <= len(menu)
    assert srv._chunker.per_sweep_ewma is not None  # costs were measured


def test_adaptive_chunks_results_bit_equal_static():
    """Chunk size never changes physics: an adaptively-chunked job equals
    the same job under the static knob, bit for bit."""
    srv_a = _server(slots=1, chunk_sweeps="adaptive")
    srv_s = _server(slots=1, chunk_sweeps=3)
    for srv in (srv_a, srv_s):
        srv.submit(AnnealJob.constant(seed=21, sweeps=11, beta=0.9))
    (ra,), (rs,) = srv_a.drain(), srv_s.drain()
    np.testing.assert_array_equal(ra.spins, rs.spins)
    assert ra.energy == rs.energy


def test_adaptive_chunker_policy():
    from repro.serve_mc import AdaptiveChunker

    ch = AdaptiveChunker(target_launch_s=0.1, max_chunk=64, init_chunk=8)
    assert ch.menu == (1, 2, 4, 8, 16, 32, 64)
    assert ch.floor_to_menu(7) == 4 and ch.floor_to_menu(64) == 64
    assert ch.floor_to_menu(0) == 1  # never below the smallest chunk
    # Before any measurement: init chunk, clamped by segment boundary.
    assert ch.propose(queue_depth=0, segment_bound=100) == 8
    assert ch.propose(queue_depth=0, segment_bound=5) == 4
    # The FIRST observation at a chunk size is the jit compile; it must be
    # discarded or the policy would collapse to chunk=1 during warm-up.
    ch.observe(chunk=8, launch_s=3.0)  # compile -> ignored
    assert ch.per_sweep_ewma is None
    assert ch.propose(queue_depth=0, segment_bound=1000) == 8
    # Cheap warm launches -> grow toward the latency target; queue shrinks.
    ch.observe(chunk=8, launch_s=0.008)  # 1 ms/sweep -> target 100 sweeps
    assert ch.propose(queue_depth=0, segment_bound=1000) == 64  # menu cap
    assert ch.propose(queue_depth=9, segment_bound=1000) <= 8
    with pytest.raises(ValueError, match="chunk_sweeps"):
        _server(slots=1, chunk_sweeps="sometimes")


# -----------------------------------------------------------------------------
# Observables.
# -----------------------------------------------------------------------------


def test_observables_match_ising_energy():
    rng = np.random.default_rng(0)
    spins = np.where(rng.random((3, MODEL.num_spins)) < 0.5, -1.0, 1.0)
    e = observables.energies(MODEL, spins)
    assert e.shape == (3,)
    for b in range(3):
        assert e[b] == ising.energy(MODEL, spins[b])
    mag = observables.magnetization(spins)
    np.testing.assert_allclose(mag, spins.mean(axis=1))
    s = observables.summarize(MODEL, spins[0])
    assert s.energy == e[0] and s.magnetization == mag[0]
    alm = observables.abs_layer_magnetization(MODEL, spins)
    assert alm.shape == (3,) and (alm >= np.abs(mag) - 1e-12).all()


def test_submit_validation():
    srv = _server(slots=2, chunk_sweeps=2)
    with pytest.raises(ValueError, match="slots"):
        srv.submit(PTJob(seed=0, betas=np.ones(3, np.float32), num_rounds=1))
    job = AnnealJob.constant(seed=0, sweeps=1)
    srv.submit(job)
    with pytest.raises(ValueError, match="submitted"):
        srv.submit(job)
    with pytest.raises(ValueError, match="segments"):
        AnnealJob(seed=0, schedule=[(0, 1.0)])
    with pytest.raises(ValueError, match="chunk_sweeps"):
        _server(slots=1, chunk_sweeps=0)


def test_stats_track_utilization():
    srv = _server(slots=4, chunk_sweeps=2)
    srv.submit(AnnealJob.constant(seed=0, sweeps=4, beta=1.0))
    srv.drain()
    st = srv.stats()
    assert st["busy_slot_sweeps"] == 4
    assert st["total_slot_sweeps"] == 16  # 3 idle slots swept alongside
    assert st["utilization"] == 0.25
    assert st["spin_flips"] == 4 * MODEL.num_spins
