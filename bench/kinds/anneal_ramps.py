"""Simulated-annealing ramps (``"kind": "anneal_ramps"``): an endless
stream of single-slot `AnnealJob`s, each a linear beta ramp from
``beta_start`` to ``beta_end``, one beta per ``sweeps_per_step`` sweeps,
over a budget drawn by `traffic.Stream`; in a multi-tenant deployment
each job runs on its own instance.

A spec is ``{"kind": "anneal", "seed", "schedule": [(sweeps, beta), ...],
"user", "instance", "arrival"}``.  At the close a sample of the jobs that
retired (drawn from the seed) is compared by their results, and a sample
of the jobs still in their slots by their state.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from harness import check, reference, traffic
from harness.reference import f32


def ramp(mix: dict, budget: int) -> dict:
    steps = budget // mix["sweeps_per_step"]
    betas = np.linspace(mix["beta_start"], mix["beta_end"], steps)
    return dict(kind="anneal", schedule=[(mix["sweeps_per_step"], float(b)) for b in betas])


def specs(mix: dict, chips: int, seed: int, instances: int) -> traffic.Stream:
    return traffic.Stream(mix, instances, seed, ramp)


def make_job(served, spec: dict):
    from repro.serve_mc import AnnealJob

    model = served.models[spec["instance"]] if served.multi else None
    return AnnealJob(spec["seed"], spec["schedule"], model=model, user=spec["user"])


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
                         energy=float(r.energy), beta=np.float32(r.extras["final_beta"])))
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
                         beta=np.asarray(srv.carry.betas[b])))
    return dict(done=done, live=live)


def _model_of(spec: dict, insts: list) -> dict:
    return insts[spec["instance"]] if len(insts) > 1 else insts[0]


def numbers(out: dict, outputs: dict, lat: reference.Lattice, insts: list, ftype=f32) -> None:
    """Adds the sampled jobs' gaps to the reference to ``out``."""
    n, L, V = lat.n, lat.L, lat.V
    jobs = outputs.get("done", []) + outputs.get("live", [])
    if not jobs:
        return
    refs = replay_anneals(lat, [
        dict(seed=j["spec"]["seed"], model=_model_of(j["spec"], insts),
             schedule=j["spec"]["schedule"], sweeps=j["sweeps"]) for j in jobs], ftype=ftype)
    for j, ref in zip(jobs, refs):
        model = _model_of(j["spec"], insts)
        ref_flat = reference.from_lane(ref["spins"], n, L, V)
        out["betas_differ"] += int(np.float32(j["beta"]).item() != ref["beta"].item())
        if "spins_flat" in j:  # a retired job's result
            out["spins_differ"] += int(np.sum(j["spins_flat"] != ref_flat))
            out["energy_gap"] = max(out["energy_gap"],
                                    check.rel(j["energy"], reference.energy_f64(model, ref_flat)))
        else:  # a job still in its slot at the close
            out["spins_differ"] += int(np.sum(j["spins"] != ref["spins"]))
            out["rng_differ"] += int(np.sum(j["rng"] != ref["rng"]))
            out["fields_gap"] = max(out["fields_gap"], check.gap(j["h_space"], ref["h_space"]),
                                    check.gap(j["h_tau"], ref["h_tau"]))
        out["compared"] += 1


def control(lat: reference.Lattice, insts: list, mix: dict, seed: int, ftype,
            sweeps: int) -> dict:
    """The first ``sample_jobs`` jobs of the seed's stream replayed to their
    end and ``sample_active`` more to half of their budget, in ``ftype``,
    in the shape a run's outputs have (``sweeps`` is not used)."""
    stream = specs(mix, 1, seed, len(insts))
    done_specs = stream.take(mix["sample_jobs"])
    live_specs = stream.take(mix["sample_active"])
    jobs = [dict(spec=s, sweeps=sum(k for k, _ in s["schedule"])) for s in done_specs]
    jobs += [dict(spec=s, sweeps=sum(k for k, _ in s["schedule"]) // 16 * 8) for s in live_specs]
    refs = replay_anneals(lat, [
        dict(seed=j["spec"]["seed"], model=insts[j["spec"]["instance"]],
             schedule=j["spec"]["schedule"], sweeps=j["sweeps"]) for j in jobs], ftype=ftype)
    done, live = [], []
    for j, r in zip(jobs, refs):
        model = insts[j["spec"]["instance"]]
        if len(done) < len(done_specs):
            flat = reference.from_lane(r["spins"], lat.n, lat.L, lat.V)
            done.append(dict(j, spins_flat=flat, energy=float(reference.energy_f64(model, flat)[0]),
                             beta=r["beta"]))
        else:
            live.append(dict(j, spins=r["spins"], h_space=r["h_space"], h_tau=r["h_tau"],
                             rng=r["rng"], beta=r["beta"]))
    return dict(done=done, live=live)


def replay_anneals(lat: reference.Lattice, jobs: list, unit: int = 8, ftype=f32) -> list:
    """Replay single-slot annealing jobs from their seeds.

    Each job is a dict with ``seed``, ``model`` (arrays), ``schedule``
    (list of (sweeps, beta)) and ``sweeps`` (how many to run).  Returns,
    per job, its final lane spins, fields, generator columns and beta.
    """
    n, L, V = lat.n, lat.L, lat.V
    spins = jnp.asarray(np.stack([
        reference.to_lane(reference.init_spins_flat(n * L, j["seed"] * 1000), n, L, V)
        for j in jobs]))
    mt = jnp.asarray(np.concatenate([reference.mt_seed(reference.lane_seeds(V, j["seed"]))
                                     for j in jobs], axis=1))
    h, J, tau = reference.tables([j["model"] for j in jobs])
    units = [j["sweeps"] // unit for j in jobs]
    if any(j["sweeps"] % unit for j in jobs):
        raise ValueError("every replayed sweep count must be a multiple of the unit")
    sched = []
    for j in jobs:  # beta of each unit of sweeps
        per = [b for s, b in j["schedule"] for _ in range(s // unit)]
        sched.append(per + [per[-1]] * (max(units) - len(per)))
    final = [None] * len(jobs)
    for t in range(max(units)):
        betas = jnp.asarray([np.float32(s[t]) for s in sched], f32)
        spins, mt = reference.run_sweeps(lat, ftype, unit, spins, mt, betas, h, J, tau)
        for k, u_k in enumerate(units):
            if u_k == t + 1:
                final[k] = (spins[k], mt[:, k * V:(k + 1) * V])
    out = []
    for k, j in enumerate(jobs):
        sp, gen = final[k]
        hs, ht = reference.fields(lat, sp[None], h[k:k + 1], J[k:k + 1], tau[k:k + 1], ftype)
        seg_end = np.cumsum([s for s, _ in j["schedule"]])
        seg = min(int(np.searchsorted(seg_end, j["sweeps"], side="right")), len(seg_end) - 1)
        out.append(dict(spins=np.asarray(sp), h_space=np.asarray(hs[0]),
                        h_tau=np.asarray(ht[0]), rng=np.asarray(gen),
                        beta=np.float32(j["schedule"][seg][1])))
    return out
