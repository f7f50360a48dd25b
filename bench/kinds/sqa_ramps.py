"""Simulated quantum annealing ramps (``"kind": "sqa_ramps"``): an endless
stream of single-slot `SQAJob`s, each a path-integral Monte Carlo anneal
of its own instance (Santoro, Martonak, Tosatti and Car, Science 295,
2427 (2002)): beta ``beta`` fixed, the transverse field Gamma linear from
``gamma_start`` to ``gamma_end``, one Gamma per ``sweeps_per_step``
sweeps, over a budget drawn by `traffic.Stream`.

The instance's L layers are its Trotter slices: they sweep h / L and
J / L, and adjacent slices couple by K = 1/2 ln coth(beta Gamma / L),
tau = K / beta, which changes with every Gamma step.  Both are computed
here from the formula in float64 and cast to float32.

A spec is ``{"kind": "sqa", "seed", "beta", "gammas": [...],
"sweeps_per_gamma", "user", "instance", "arrival"}``.  At the close a
sample of the jobs that retired (drawn from the seed) is compared by
their results (final slices, their layered energy, the layer-majority
assignment and its problem energy; an assignment's differing sites
count in ``spins_differ``), and a sample of the jobs still in their
slots by their state and their slot's tau row.  The fields of a job in
its slot are those of the last launch, under the Gamma it swept; its tau
row is already the next Gamma's.
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

from harness import check, reference, traffic
from harness.reference import f32

LIMITS = {"tau_differ": (0, "a slot's tau row is its schedule's next value, cast to "
                            "float32, in every entry of tau and of its double")}


def ramp(mix: dict, budget: int) -> dict:
    steps = budget // mix["sweeps_per_step"]
    gammas = np.linspace(mix["gamma_start"], mix["gamma_end"], steps)
    return dict(kind="sqa", beta=float(mix["beta"]), gammas=[float(g) for g in gammas],
                sweeps_per_gamma=mix["sweeps_per_step"])


def specs(mix: dict, chips: int, seed: int, instances: int) -> traffic.Stream:
    return traffic.Stream(mix, instances, seed, ramp)


def make_job(served, spec: dict):
    from repro.serve_mc import SQAJob

    model = served.models[spec["instance"]] if served.multi else None
    return SQAJob(spec["seed"], spec["beta"], spec["gammas"], spec["sweeps_per_gamma"],
                  model=model, user=spec["user"])


def tau(beta: float, gamma: float, L: int) -> np.float32:
    """The tau coupling of adjacent Trotter slices, in energy units."""
    k = 0.5 * math.log(1.0 / math.tanh(beta * gamma / L))
    return np.float32(k / beta)


def sliced(inst: dict) -> dict:
    """The instance's fields and couplings over its L Trotter slices."""
    L = inst["L"]
    return dict(inst, h=(np.asarray(inst["h"], np.float64) / L).astype(np.float32),
                space_J=(np.asarray(inst["space_J"], np.float64) / L).astype(np.float32))


def majority(flat: np.ndarray, n: int, L: int) -> np.ndarray:
    """Per site, the sign of its sum over the slices (+1 on a tie)."""
    return np.where(np.asarray(flat, np.float64).reshape(L, n).sum(axis=0) >= 0, 1.0, -1.0)


def problem_energy(inst: dict, assign: np.ndarray) -> float:
    s = np.asarray(assign, np.float64)
    nbr, J = np.asarray(inst["space_nbr"]), np.asarray(inst["space_J"], np.float64)
    e = -float((np.asarray(inst["h"], np.float64) * s).sum())
    for d in range(nbr.shape[1]):
        e -= 0.5 * float((J[:, d] * s * s[nbr[:, d]]).sum())
    return e


def outputs(served, loop, pool: list, mix: dict, rng) -> dict:
    """``sample_jobs`` retired jobs of ``pool`` by their results, and
    ``sample_active`` jobs still in their slots by their state."""
    srv = served.server
    eng = srv.engine
    done = []
    for jid in sorted(rng.choice(pool, size=min(mix["sample_jobs"], len(pool)), replace=False)):
        spec, job = served.jobs[int(jid)]
        r = loop.retired[int(jid)][0]
        done.append(dict(spec=spec, sweeps=job.sweeps_done, spins_flat=np.asarray(r.spins),
                         energy=float(r.energy), assignment=np.asarray(r.extras["assignment"]),
                         problem_energy=float(r.extras["problem_energy"])))
    active = [job for _, job in served.jobs.values()
              if job.jid in srv._active and job.sweeps_done > 0]
    live = []
    picks = rng.choice(len(active), size=min(mix["sample_active"], len(active)), replace=False)
    for k in sorted(picks):
        job = active[k]
        spec = served.jobs[job.jid][0]
        b = int(eng.phys_slots(served.slots_of(job))[0])
        V = eng.V
        live.append(dict(spec=spec, sweeps=job.sweeps_done,
                         spins=np.asarray(srv.carry.spins[b]), h_space=np.asarray(srv.carry.h_space[b]),
                         h_tau=np.asarray(srv.carry.h_tau[b]), rng=np.asarray(srv.carry.rng[:, b * V:(b + 1) * V]),
                         beta=np.asarray(srv.carry.betas[b]),
                         tau_J=np.asarray(eng.slot_tables["tau_J"][b]),
                         tau_J2=np.asarray(eng.slot_tables["tau_J2"][b])))
    return dict(done=done, live=live)


def _inst_of(spec: dict, insts: list) -> dict:
    return insts[spec["instance"]] if len(insts) > 1 else insts[0]


def numbers(out: dict, outputs: dict, lat: reference.Lattice, insts: list, ftype=f32) -> None:
    """Adds the sampled jobs' gaps to the reference to ``out``."""
    n, L, V = lat.n, lat.L, lat.V
    jobs = outputs.get("done", []) + outputs.get("live", [])
    if not jobs:
        return
    refs = replay_sqa(lat, [dict(j["spec"], inst=_inst_of(j["spec"], insts), sweeps=j["sweeps"])
                            for j in jobs], ftype=ftype)
    for j, ref in zip(jobs, refs):
        spec = j["spec"]
        ref_flat = reference.from_lane(ref["spins"], n, L, V)
        if "spins_flat" in j:  # a retired job's result
            inst = _inst_of(spec, insts)
            model = dict(sliced(inst), tau_J=np.full(n, ref["tau"], np.float32))
            assign = majority(ref_flat, n, L)
            out["spins_differ"] += int(np.sum(j["spins_flat"] != ref_flat))
            out["spins_differ"] += int(np.sum(j["assignment"] != assign))
            out["energy_gap"] = max(out["energy_gap"],
                                    check.rel(j["energy"], reference.energy_f64(model, ref_flat)),
                                    check.rel(j["problem_energy"], problem_energy(inst, assign)))
        else:  # a job still in its slot at the close
            out["spins_differ"] += int(np.sum(j["spins"] != ref["spins"]))
            out["rng_differ"] += int(np.sum(j["rng"] != ref["rng"]))
            out["betas_differ"] += int(np.float32(j["beta"]).item() != np.float32(spec["beta"]).item())
            out["fields_gap"] = max(out["fields_gap"], check.gap(j["h_space"], ref["h_space"]),
                                    check.gap(j["h_tau"], ref["h_tau"]))
            nxt = tau(spec["beta"], spec["gammas"][j["sweeps"] // spec["sweeps_per_gamma"]], L)
            out["tau_differ"] += int(np.sum(j["tau_J"] != nxt))
            out["tau_differ"] += int(np.sum(j["tau_J2"] != np.float32(2.0) * nxt))
        out["compared"] += 1


def control(lat: reference.Lattice, insts: list, mix: dict, seed: int, ftype,
            sweeps: int) -> dict:
    """The first ``sample_jobs`` jobs of the seed's stream replayed to their
    end and ``sample_active`` more to half of their budget, in ``ftype``,
    in the shape a run's outputs have (``sweeps`` is not used)."""
    stream = specs(mix, 1, seed, len(insts))
    done_specs = stream.take(mix["sample_jobs"])
    live_specs = stream.take(mix["sample_active"])
    unit = mix["sweeps_per_step"]
    jobs = [dict(spec=s, sweeps=unit * len(s["gammas"])) for s in done_specs]
    jobs += [dict(spec=s, sweeps=unit * (len(s["gammas"]) // 2)) for s in live_specs]
    refs = replay_sqa(lat, [dict(j["spec"], inst=insts[j["spec"]["instance"]], sweeps=j["sweeps"])
                            for j in jobs], ftype=ftype)
    done, live = [], []
    for j, r in zip(jobs, refs):
        spec = j["spec"]
        inst = insts[spec["instance"]]
        if len(done) < len(done_specs):
            flat = reference.from_lane(r["spins"], lat.n, lat.L, lat.V)
            model = dict(sliced(inst), tau_J=np.full(lat.n, r["tau"], np.float32))
            assign = majority(flat, lat.n, lat.L)
            done.append(dict(j, spins_flat=flat, energy=float(reference.energy_f64(model, flat)[0]),
                             assignment=assign, problem_energy=problem_energy(inst, assign)))
        else:
            nxt = tau(spec["beta"], spec["gammas"][j["sweeps"] // unit], lat.L)
            live.append(dict(j, spins=r["spins"], h_space=r["h_space"], h_tau=r["h_tau"],
                             rng=r["rng"], beta=np.float32(spec["beta"]),
                             tau_J=np.full(lat.n, nxt, np.float32),
                             tau_J2=np.full(lat.n, np.float32(2.0) * nxt, np.float32)))
    return dict(done=done, live=live)


def replay_sqa(lat: reference.Lattice, jobs: list, ftype=f32) -> list:
    """Replay single-slot SQA jobs from their seeds, in units of their
    ``sweeps_per_gamma`` sweeps, each unit under its own Gamma's tau row.

    Each job is a spec with ``inst`` (its instance's arrays) and
    ``sweeps`` (how many to run).  Returns, per job, its final lane
    spins, generator columns, the fields under the tau row of its last
    unit, and that tau.
    """
    n, L, V = lat.n, lat.L, lat.V
    unit = jobs[0]["sweeps_per_gamma"]
    if any(j["sweeps_per_gamma"] != unit or j["sweeps"] % unit for j in jobs):
        raise ValueError("every replayed job must run whole units of one size")
    spins = jnp.asarray(np.stack([
        reference.to_lane(reference.init_spins_flat(n * L, j["seed"] * 1000), n, L, V)
        for j in jobs]))
    mt = jnp.asarray(np.concatenate([reference.mt_seed(reference.lane_seeds(V, j["seed"]))
                                     for j in jobs], axis=1))
    h, J, _ = reference.tables([sliced(j["inst"]) for j in jobs])
    betas = jnp.asarray([np.float32(j["beta"]) for j in jobs], f32)
    units = [j["sweeps"] // unit for j in jobs]
    taus = [[tau(j["beta"], g, L) for g in j["gammas"]] for j in jobs]
    final = [None] * len(jobs)
    for t in range(max(units)):
        row = [tj[min(t, len(tj) - 1)] for tj in taus]
        tau_t = jnp.asarray(np.repeat(np.asarray(row, np.float32)[:, None], n, axis=1))
        spins, mt = reference.run_sweeps(lat, ftype, unit, spins, mt, betas, h, J, tau_t)
        for k, u_k in enumerate(units):
            if u_k == t + 1:
                final[k] = (spins[k], mt[:, k * V:(k + 1) * V], row[k])
    out = []
    for k in range(len(jobs)):
        sp, gen, tau_k = final[k]
        tau_row = jnp.full((1, n), tau_k, f32)
        hs, ht = reference.fields(lat, sp[None], h[k:k + 1], J[k:k + 1], tau_row, ftype)
        out.append(dict(spins=np.asarray(sp), h_space=np.asarray(hs[0]), h_tau=np.asarray(ht[0]),
                        rng=np.asarray(gen), tau=tau_k))
    return out
