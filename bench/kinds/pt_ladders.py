"""Parallel-tempering ladders (``"kind": "pt_ladders"``): a closed, fixed
set of `PTJob`s, one ladder of ``rungs`` replicas each, none retiring in
a window.

A spec is ``{"kind": "pt", "seed", "betas", "rounds", "sweeps_per_round"}``.
At the close every ladder is read (its state in the carry, or the result
of one that has retired) and replayed in full: each holds a block of
slots, so a fault in any part of the batch shows in some ladder.

The replay adds the tempering swap to the shared reference: every 16
sweeps the adjacent pairs of one parity propose a beta exchange with
probability ``min(1, exp(dbeta * dE))``, energies summed in one fixed
pairwise order, one uniform per pair from a scalar MT19937 seeded
``seed + 17``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness import check, reference, traffic
from harness.reference import f32


def specs(mix: dict, chips: int, seed: int, instances: int) -> list:
    rng = np.random.default_rng([seed, 1])
    if mix["spacing"] != "geometric":
        raise ValueError(f"unknown beta spacing {mix['spacing']!r}")
    betas = np.geomspace(mix["beta_min"], mix["beta_max"], mix["rungs"]).astype(np.float32)
    per = mix["sweeps_per_round"]
    if mix["sweeps"] % per:
        raise ValueError("sweeps must be a whole number of rounds")
    return [dict(kind="pt", seed=int(rng.integers(traffic.SEED_SPACE)), betas=betas,
                 rounds=mix["sweeps"] // per, sweeps_per_round=per)
            for _ in range(mix["ladders_per_chip"] * chips)]


def make_job(served, spec: dict):
    from repro.serve_mc import PTJob

    return PTJob(spec["seed"], spec["betas"], num_rounds=spec["rounds"],
                 sweeps_per_round=spec["sweeps_per_round"])


def outputs(served, loop, pool: list, mix: dict, rng) -> dict:
    """Every ladder at the close: its state in the carry, or the result of
    one that has retired."""
    srv = served.server
    eng = srv.engine
    out = []
    for spec, job in served.jobs.values():
        lad = dict(kind="pt", seed=spec["seed"], betas0=spec["betas"],
                   sweeps=job.sweeps_done, sweeps_per_round=spec["sweeps_per_round"])
        if job.jid in loop.retired:
            result = loop.retired[job.jid][0]
            out.append(dict(lad, betas=np.asarray(result.extras["betas"]),
                            energy=np.asarray(result.energy), spins_flat=np.asarray(result.spins),
                            swap_accept=result.extras["swap_accept"],
                            swap_propose=result.extras["swap_propose"]))
            continue
        slots = served.slots_of(job)
        idx = eng.phys_slots(slots)
        V = eng.V
        cols = np.concatenate([np.arange(b * V, (b + 1) * V) for b in idx])
        result = job.finalize(srv, slots)
        out.append(dict(
            lad, spins=np.asarray(srv.carry.spins[idx]), h_space=np.asarray(srv.carry.h_space[idx]),
            h_tau=np.asarray(srv.carry.h_tau[idx]), rng=np.asarray(srv.carry.rng[:, cols]),
            betas=np.asarray(srv.carry.betas[idx]), energy=np.asarray(result.energy),
            spins_flat=np.asarray(result.spins), swap_accept=int(job.swap_accept),
            swap_propose=int(job.swap_propose)))
    return dict(ladders=out)


def numbers(out: dict, outputs: dict, lat: reference.Lattice, insts: list, ftype=f32) -> None:
    """Adds the ladders' gaps to the reference to ``out``."""
    n, L, V = lat.n, lat.L, lat.V
    ladders = outputs.get("ladders", [])
    groups = {}  # ladders that ran alike replay in one batch
    for k, lad in enumerate(ladders):
        groups.setdefault((lad["sweeps"], lad["sweeps_per_round"], len(lad["betas0"])), []).append(k)
    refs = {}
    for (sweeps, per_round, _), ks in groups.items():
        batch = replay_ladders(
            lat, insts[0], [(ladders[k]["seed"], ladders[k]["betas0"]) for k in ks],
            sweeps, per_round, ftype=ftype)
        refs.update(zip(ks, batch))
    for k, lad in enumerate(ladders):
        ref = refs[k]
        if "spins" in lad:  # a ladder still in its slots: its whole state
            out["spins_differ"] += int(np.sum(lad["spins"] != ref["spins"]))
            out["rng_differ"] += int(np.sum(lad["rng"] != ref["rng"]))
            out["fields_gap"] = max(out["fields_gap"], check.gap(lad["h_space"], ref["h_space"]),
                                    check.gap(lad["h_tau"], ref["h_tau"]))
        out["spins_differ"] += int(np.sum(lad["spins_flat"] != reference.from_lane(ref["spins"], n, L, V)))
        out["betas_differ"] += int(np.sum(lad["betas"] != ref["betas"]))
        out["swaps_differ"] += (abs(lad["swap_accept"] - ref["swap_accept"])
                                + abs(lad["swap_propose"] - ref["swap_propose"]))
        e_ref = reference.energy_f64(insts[0], reference.from_lane(ref["spins"], n, L, V))
        out["energy_gap"] = max(out["energy_gap"], check.rel(lad["energy"], e_ref))
        out["compared"] += len(lad["betas0"])


def control(lat: reference.Lattice, insts: list, mix: dict, seed: int, ftype,
            sweeps: int) -> dict:
    """Every ladder of one chip's share of the seed's traffic, replayed for
    ``sweeps`` sweeps in ``ftype``, in the shape a run's outputs have."""
    ladders = specs(mix, 1, seed, len(insts))
    refs = replay_ladders(lat, insts[0], [(s["seed"], s["betas"]) for s in ladders],
                          sweeps, ladders[0]["sweeps_per_round"], ftype=ftype)
    out = []
    for spec, ref in zip(ladders, refs):
        flat = reference.from_lane(ref["spins"], lat.n, lat.L, lat.V)
        out.append(dict(
            kind="pt", seed=spec["seed"], betas0=spec["betas"], sweeps=sweeps,
            sweeps_per_round=spec["sweeps_per_round"], spins=ref["spins"],
            h_space=ref["h_space"], h_tau=ref["h_tau"], rng=ref["rng"], betas=ref["betas"],
            spins_flat=flat, energy=reference.energy_f64(insts[0], flat),
            swap_accept=ref["swap_accept"], swap_propose=ref["swap_propose"]))
    return dict(ladders=out)


def replay_ladders(lat: reference.Lattice, model: dict, ladders: list, sweeps: int,
                   sweeps_per_round: int, unit: int = 8, ftype=f32) -> list:
    """Replay parallel-tempering ladders of one model from their seeds, all
    in one batch.  ``ladders`` is a list of ``(seed, betas)``, every ladder
    with the same number of rungs.  In a ladder seeded ``seed``, replica
    ``b`` starts from the spins seeded ``seed * 1000 + b`` and the generator
    columns ``lane_seeds(R * V, seed)[b * V:(b + 1) * V]``; a swap phase
    follows every ``sweeps_per_round`` sweeps, parity alternating from 0,
    with the ladder's own swap generator seeded ``seed + 17``."""
    n, L, V = lat.n, lat.L, lat.V
    K, R = len(ladders), len(ladders[0][1])
    if any(len(b) != R for _, b in ladders):
        raise ValueError("every ladder of a batch must have the same number of rungs")
    spins = jnp.asarray(np.stack([
        reference.to_lane(reference.init_spins_flat(n * L, seed * 1000 + b), n, L, V)
        for seed, _ in ladders for b in range(R)]))
    mt = jnp.asarray(np.concatenate([reference.mt_seed(reference.lane_seeds(R * V, seed))
                                     for seed, _ in ladders], axis=1))
    swap_mt = jnp.asarray(np.stack([reference.mt_seed(np.uint32(seed + 17)) for seed, _ in ladders]))
    h1, J1, tau1 = reference.tables([model])
    h, J, tau = (jnp.broadcast_to(x, (K * R,) + x.shape[1:]) for x in (h1, J1, tau1))
    b = jnp.asarray(np.stack([np.asarray(betas, np.float32) for _, betas in ladders]))
    swap = jax.vmap(_swap, in_axes=(0, 0, 0, None))
    accepted = proposed = jnp.zeros((K,), jnp.int32)
    per_round = sweeps_per_round // unit
    for t in range(sweeps // unit):
        spins, mt = reference.run_sweeps(lat, ftype, unit, spins, mt, b.reshape(-1), h, J, tau)
        if (t + 1) % per_round == 0:
            r = (t + 1) // per_round - 1
            e = swap_energies(lat, ftype, spins, h1[0], J1[0], tau1[0]).reshape(K, R)
            b, swap_mt, acc, prop = swap(b, e, swap_mt, jnp.int32(r % 2))
            accepted, proposed = accepted + acc, proposed + prop
    hs, ht = reference.fields(lat, spins, h, J, tau, ftype)
    spins, hs, ht, mt = (np.asarray(x) for x in (spins, hs, ht, mt))
    b, accepted, proposed = np.asarray(b), np.asarray(accepted), np.asarray(proposed)
    return [dict(spins=spins[k * R:(k + 1) * R], h_space=hs[k * R:(k + 1) * R],
                 h_tau=ht[k * R:(k + 1) * R], rng=mt[:, k * R * V:(k + 1) * R * V],
                 betas=b[k], swap_accept=int(accepted[k]), swap_propose=int(proposed[k]))
            for k in range(K)]


# -----------------------------------------------------------------------------
# The swap phase of the reference: energies in one fixed pairwise order,
# then the exchange of adjacent pairs of one parity.
# -----------------------------------------------------------------------------


def _pairwise(x):
    x = jnp.ravel(x)
    size = 1 << max(x.size - 1, 0).bit_length()
    x = jnp.pad(x, (0, size - x.size))
    while x.size > 1:
        x = x[: x.size // 2] + x[x.size // 2:]
    return x[0]


@functools.partial(jax.jit, static_argnums=(0, 1))
def swap_energies(lat: reference.Lattice, ftype, spins, h, J, tau):
    """(B,) energies of (B, rows, V) spins, one model, summed in a fixed
    pairwise order (the tempering swap's energies)."""
    def one(sp):
        s = sp.reshape(lat.lpv, lat.n, lat.V).astype(ftype)
        t = h[None, :, None].astype(ftype) * s
        for d in range(lat.sd):
            t = t + 0.5 * J[None, :, d, None].astype(ftype) * s * s[:, lat.nbr[:, d], :]
        up = jnp.concatenate([s[1:], jnp.roll(s[:1], -1, axis=-1)], axis=0)
        t = t + tau[None, :, None].astype(ftype) * s * up
        return -_pairwise(t)
    return jax.vmap(one)(spins).astype(f32)


@jax.jit
def _swap(betas, energies, swap_mt, parity):
    R = betas.shape[0]
    swap_mt, su = reference.uniforms(swap_mt[:, None], (R + 1) // 2)
    swap_mt, su = swap_mt[:, 0], su[:, 0]
    idx = jnp.arange(R)
    left = (idx % 2 == parity) & (idx + 1 < R)
    partner = jnp.clip(jnp.where(left, idx + 1, jnp.where(idx % 2 != parity, idx - 1, idx)), 0, R - 1)
    valid = partner != idx
    p_acc = reference.fast_exp(jnp.clip((betas - betas[partner]) * (energies - energies[partner]), -20.0, 0.0))
    u = su[idx // 2]
    u = jnp.where(left, u, u[partner])
    accept = valid & (u < p_acc)
    return (jnp.where(accept, betas[partner], betas), swap_mt,
            jnp.sum(accept.astype(jnp.int32)) // 2, jnp.sum((valid & left).astype(jnp.int32)))
