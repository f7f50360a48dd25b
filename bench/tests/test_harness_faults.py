"""The check fails a run whose timed path is broken underneath, once for
each fault a one-chip cell can have, and fails the control: the plain
reference in bfloat16 put in the program's place."""

from __future__ import annotations

import numpy as np
import pytest

from harness import check, reference, spec

CELLS = ["ladders", "anneal-backlog"]


def _unchanged(monkeypatch):
    from repro.core import engine

    monkeypatch.setattr(engine.SweepEngine, "run", lambda self, carry, n: carry)


def _half_batch(monkeypatch):
    from repro.core import engine

    orig = engine.SweepEngine.run

    def run(self, carry, n):
        new = orig(self, carry, n)
        half = new.spins.shape[0] // 2
        cols = half * (new.rng.shape[1] // new.spins.shape[0])
        return engine.SweepCarry(
            new.spins.at[half:].set(carry.spins[half:]),
            new.h_space.at[half:].set(carry.h_space[half:]),
            new.h_tau.at[half:].set(carry.h_tau[half:]),
            new.betas, new.rng.at[:, cols:].set(carry.rng[:, cols:]))

    monkeypatch.setattr(engine.SweepEngine, "run", run)


def _altered_answer(monkeypatch):
    from repro.serve_mc import jobs

    for cls in (jobs.AnnealJob, jobs.PTJob):
        orig = cls.finalize

        def finalize(self, server, slots, _orig=orig):
            r = _orig(self, server, slots)
            spins = np.array(r.spins)
            spins.reshape(-1)[0] *= -1
            return r._replace(spins=spins)

        monkeypatch.setattr(cls, "finalize", finalize)


FAULTS = {"state_unchanged": _unchanged, "half_batch_left_out": _half_batch,
          "answer_altered": _altered_answer}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_a_broken_timed_path_is_not_correct(tiny_run, monkeypatch, name, fault):
    FAULTS[fault](monkeypatch)
    rec = tiny_run(name)
    assert not rec["checks"]["correct"], rec["checks"]


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_the_bfloat16_control_is_not_correct(tiny_cell, seed):
    from harness import models

    c, cfg, mix = tiny_cell("ladders")
    kind = spec.kind(mix["kind"])
    insts = models.instances(cfg, seed)
    lat = reference.Lattice(insts[0]["n"], insts[0]["L"], 4, insts[0]["space_nbr"])
    ctrl = kind.control(lat, insts, mix, seed, reference.jnp.bfloat16, 256)
    ok, checks = check.verdict(check.numbers(ctrl, lat, insts, 0, kind), kind)
    assert not ok and checks["spins_differ"]["value"] > 0
    same = kind.control(lat, insts, mix, seed, reference.f32, 256)
    ok, _ = check.verdict(check.numbers(same, lat, insts, 0, kind), kind)
    assert ok
