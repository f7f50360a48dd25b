"""The comparison that decides ``correct``: what the window produced
against the plain reference, number by number, each against its limit.
The job kind's module (``bench/kinds/<kind>.py``) replays its jobs and
adds their gaps; the limits here hold for every kind.  The readings each
limit was set from are in PERF.md.

Numbers (a run is correct when every number is at or below its limit):

* ``jobs_lost``     jobs due in the window that never retired;
* ``spins_differ``  spins of the sampled replicas that differ;
* ``rng_differ``    MT19937 state words that differ;
* ``betas_differ``  replica betas that differ (exchanges, schedule steps);
* ``swaps_differ``  replica exchanges accepted and proposed that differ;
* ``fields_gap``    largest absolute gap of the carried effective fields;
* ``energy_gap``    largest gap of a reported energy, relative to the
                    reference energy's magnitude (at least 1).

A kind may compare a number of its own: its module's ``LIMITS`` maps the
name to ``(limit, reason)``.  It may not name a number above.
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


def rel(a, b) -> float:
    a, b = np.atleast_1d(np.asarray(a, np.float64)), np.atleast_1d(np.asarray(b, np.float64))
    return float(np.max(np.abs(a - b) / np.maximum(1.0, np.abs(b)))) if a.size else 0.0


def gap(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))))


def limits(kind) -> dict:
    """The shared limits and the kind's own."""
    own = {name: lim for name, (lim, _reason) in getattr(kind, "LIMITS", {}).items()}
    if set(own) & set(LIMITS):
        raise ValueError(f"a job kind may not change a shared limit: {sorted(set(own) & set(LIMITS))}")
    return dict(LIMITS, **own)


def numbers(outputs: dict, lat: reference.Lattice, insts: list, lost: int, kind,
            ftype=reference.f32) -> dict:
    """The compared numbers (no verdict) of one run's outputs."""
    out = dict(jobs_lost=lost, spins_differ=0, rng_differ=0, betas_differ=0,
               swaps_differ=0, fields_gap=0.0, energy_gap=0.0, compared=0)
    out.update({name: 0 for name in limits(kind) if name not in LIMITS})
    kind.numbers(out, outputs, lat, insts, ftype)
    return out


def verdict(nums: dict, kind) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) for the numbers of a run."""
    checks = {k: {"value": nums[k], "limit": lim} for k, lim in limits(kind).items()}
    ok = nums["compared"] > 0 and all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


def compare(outputs: dict, lat: reference.Lattice, insts: list, lost: int, kind) -> dict:
    nums = numbers(outputs, lat, insts, lost, kind)
    ok, checks = verdict(nums, kind)
    return dict(correct=ok, checks=checks, compared=nums["compared"])
