"""The comparison that decides ``correct``: what the window produced
against the plain reference (`harness.reference`), number by number,
each against its limit.  The readings each limit was set from are in
PERF.md.

Numbers (a run is correct when every number is at or below its limit):

* ``jobs_lost``     jobs due in the window that never retired;
* ``spins_differ``  spins of the sampled replicas that differ;
* ``rng_differ``    MT19937 state words that differ;
* ``betas_differ``  replica betas that differ (PT swaps, anneal steps);
* ``swaps_differ``  PT swap acceptances and proposals that differ;
* ``fields_gap``    largest absolute gap of the carried effective fields;
* ``energy_gap``    largest gap of a reported energy, relative to the
                    reference energy's magnitude (at least 1).
"""

from __future__ import annotations

import numpy as np

from harness import reference

LIMITS = {
    "jobs_lost": 0,
    "spins_differ": 0,
    "rng_differ": 0,
    "betas_differ": 0,
    "swaps_differ": 0,
    "fields_gap": 1e-5,
    "energy_gap": 1e-9,
}


def _rel(a, b) -> float:
    a, b = np.atleast_1d(np.asarray(a, np.float64)), np.atleast_1d(np.asarray(b, np.float64))
    return float(np.max(np.abs(a - b) / np.maximum(1.0, np.abs(b)))) if a.size else 0.0


def _gap(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))))


def _model_of(spec: dict, insts: list) -> dict:
    return insts[spec["instance"]] if len(insts) > 1 else insts[0]


def numbers(outputs: dict, lat: reference.Lattice, insts: list, lost: int,
            ftype=reference.f32) -> dict:
    """The compared numbers (no verdict) of one run's outputs."""
    n, L, V = lat.n, lat.L, lat.V
    out = dict(jobs_lost=lost, spins_differ=0, rng_differ=0, betas_differ=0,
               swaps_differ=0, fields_gap=0.0, energy_gap=0.0, compared=0)
    ladders = outputs.get("ladders", [])
    groups = {}  # ladders that ran alike replay in one batch
    for k, lad in enumerate(ladders):
        groups.setdefault((lad["sweeps"], lad["sweeps_per_round"], len(lad["betas0"])), []).append(k)
    refs = {}
    for (sweeps, per_round, _), ks in groups.items():
        batch = reference.replay_ladders(
            lat, insts[0], [(ladders[k]["seed"], ladders[k]["betas0"]) for k in ks],
            sweeps, per_round, ftype=ftype)
        refs.update(zip(ks, batch))
    for k, lad in enumerate(ladders):
        ref = refs[k]
        out["spins_differ"] += int(np.sum(lad["spins"] != ref["spins"]))
        out["spins_differ"] += int(np.sum(lad["spins_flat"] != reference.from_lane(ref["spins"], n, L, V)))
        out["rng_differ"] += int(np.sum(lad["rng"] != ref["rng"]))
        out["betas_differ"] += int(np.sum(lad["betas"] != ref["betas"]))
        out["swaps_differ"] += (abs(lad["swap_accept"] - ref["swap_accept"])
                                + abs(lad["swap_propose"] - ref["swap_propose"]))
        out["fields_gap"] = max(out["fields_gap"], _gap(lad["h_space"], ref["h_space"]),
                                _gap(lad["h_tau"], ref["h_tau"]))
        e_ref = reference.energy_f64(insts[0], reference.from_lane(ref["spins"], n, L, V))
        out["energy_gap"] = max(out["energy_gap"], _rel(lad["energy"], e_ref))
        out["compared"] += len(lad["betas0"])
    jobs = outputs.get("done", []) + outputs.get("live", [])
    if jobs:
        refs = reference.replay_anneals(lat, [
            dict(seed=j["spec"]["seed"], model=_model_of(j["spec"], insts),
                 schedule=j["spec"]["schedule"], sweeps=j["sweeps"]) for j in jobs], ftype=ftype)
        for j, ref in zip(jobs, refs):
            model = _model_of(j["spec"], insts)
            ref_flat = reference.from_lane(ref["spins"], n, L, V)
            out["betas_differ"] += int(np.float32(j["beta"]).item() != ref["beta"].item())
            if "spins_flat" in j:  # a retired job's result
                out["spins_differ"] += int(np.sum(j["spins_flat"] != ref_flat))
                out["energy_gap"] = max(out["energy_gap"],
                                        _rel(j["energy"], reference.energy_f64(model, ref_flat)))
            else:  # a job still in its slot at the close
                out["spins_differ"] += int(np.sum(j["spins"] != ref["spins"]))
                out["rng_differ"] += int(np.sum(j["rng"] != ref["rng"]))
                out["fields_gap"] = max(out["fields_gap"], _gap(j["h_space"], ref["h_space"]),
                                        _gap(j["h_tau"], ref["h_tau"]))
            out["compared"] += 1
    return out


def verdict(nums: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) for the numbers of a run."""
    checks = {k: {"value": nums[k], "limit": lim} for k, lim in LIMITS.items()}
    ok = nums["compared"] > 0 and all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


def compare(outputs: dict, lat: reference.Lattice, insts: list, lost: int) -> dict:
    nums = numbers(outputs, lat, insts, lost)
    ok, checks = verdict(nums)
    return dict(correct=ok, checks=checks, compared=nums["compared"])
