"""The ``sqa_ramps`` kind (``bench/kinds/sqa_ramps.py``) served through the
kind interface on a tiny ``aqua-multi.sqa-backlog`` cell (a 6 x 8
lattice, the jnp backend, eight slots): a sound run is correct, and the
check fails a run whose tau rows lag one Gamma step, one that sweeps
every job under its instance's own tau couplings, one that leaves half
the slots unswept, and the bfloat16 control.  `sqa_rewrite_ms` reads the
``engine.set_tau`` span."""

from __future__ import annotations

import copy
import dataclasses

import numpy as np
import pytest

from harness import check, models, reference, spec, tracing

CELL = "aqua-multi.sqa-backlog"
BENCH = spec.load_benchmark()
SEEDS = [3, 2**31 + 5]


def tiny_sqa():
    """(cell, configuration, mix) of the SQA cell cut to a CPU test's size,
    with samples as large as the tiny pool."""
    c, cfg, mix = spec.resolve(BENCH, CELL)
    cfg = copy.deepcopy(cfg)
    cfg["model"].update(spins_per_layer=6, num_layers=8)
    cfg["serve"]["backend"] = "jnp"
    cfg.update(slots_per_chip=8, instances=8)
    mix = dict(mix, budget_min=64, budget_max=128, users=4, warmup_sweeps=128,
               sample_jobs=1000, sample_active=8)
    return dict(c), cfg, mix


def test_the_cell_resolves_to_the_kind_and_its_ramps_follow_the_mix():
    c, cfg, mix = spec.resolve(BENCH, CELL)
    kind = spec.kind(mix["kind"])
    assert check.limits(kind)["tau_differ"] == 0
    for s in kind.specs(mix, 1, 2**31 + 77, cfg["instances"]).take(40):
        steps = len(s["gammas"])
        assert 2048 <= steps * s["sweeps_per_gamma"] <= 4096 and s["sweeps_per_gamma"] == 8
        assert s["gammas"][0] == 3.0 and s["gammas"][-1] == 0.05 and s["beta"] == 2.0
        assert np.all(np.diff(s["gammas"]) < 0)


def test_the_tau_row_is_the_formula_cast_to_float32():
    from repro.core import qmc

    kind = spec.kind("sqa_ramps")
    for g in (3.0, 1.234567, 0.05):
        assert kind.tau(2.0, g, 256) == np.float32(qmc.tau_coupling(2.0, g, 256))


@pytest.mark.parametrize("seed", SEEDS)
def test_a_sound_tiny_sqa_run_is_correct(cell_run, seed):
    rec = cell_run(*tiny_sqa(), seed=seed)
    assert rec["checks"]["correct"], rec["checks"]
    assert rec["checks"]["compared"] > 0 and rec["retired_window"]
    assert all(v["value"] == 0 for v in rec["checks"]["checks"].values())
    assert rec["spin_updates"] == rec["busy_slot_sweeps"] * rec["spins_per_replica"] > 0
    assert rec["compiles_window"] == 0


def _lagging_tau(monkeypatch):
    from repro.serve_mc import jobs

    monkeypatch.setattr(jobs.SQAJob, "current_gamma", lambda self: self.gammas[
        max(min(self._seg, len(self.gammas) - 1) - 1, 0)])


def _instance_tau(monkeypatch):
    from repro.serve_mc import jobs

    model_on = jobs.SQAJob.model_on
    monkeypatch.setattr(jobs.SQAJob, "model_on", lambda self, server: dataclasses.replace(
        model_on(self, server), tau_J=self.model.tau_J))
    monkeypatch.setattr(jobs.SQAJob, "tau_row", lambda self, server: self.model.tau_J)


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


FAULTS = {"tau_lags_one_gamma": _lagging_tau, "swept_under_instance_tau": _instance_tau,
          "half_batch_left_out": _half_batch}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_sqa_path_is_not_correct(cell_run, monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    rec = cell_run(*tiny_sqa())
    checks = rec["checks"]["checks"]
    assert not rec["checks"]["correct"], checks
    assert checks["spins_differ"]["value"] > 0
    if fault != "half_batch_left_out":
        assert checks["tau_differ"]["value"] > 0


@pytest.mark.parametrize("seed", SEEDS)
def test_the_bfloat16_control_is_not_correct(seed):
    c, cfg, mix = tiny_sqa()
    mix.update(sample_jobs=6, sample_active=3)
    kind = spec.kind(mix["kind"])
    insts = models.instances(cfg, seed)
    lat = reference.Lattice(insts[0]["n"], insts[0]["L"], 4, insts[0]["space_nbr"])
    ok, checks = check.verdict(check.numbers(
        kind.control(lat, insts, mix, seed, reference.jnp.bfloat16, 0), lat, insts, 0, kind), kind)
    assert not ok and checks["spins_differ"]["value"] > 0
    ok, checks = check.verdict(check.numbers(
        kind.control(lat, insts, mix, seed, reference.f32, 0), lat, insts, 0, kind), kind)
    assert ok, checks


def test_sqa_rewrite_ms_reads_the_set_tau_span_and_nothing_else():
    read = spec.reader("sqa_rewrite_ms.sqa")
    assert read({"trace": {"spans": {"engine.set_tau": [0.001, 0.003]}}}) == pytest.approx(2.0)
    assert read({"trace": {"spans": {"engine.set_betas": [0.004]}}}) is None
    # A program without the span (the anneal backlog's chip sample) reads nothing.
    sample = spec.BENCH_DIR / "trace_sample" / "aqua-multi.anneal-backlog.json.gz"
    assert read({"trace": tracing.reduce(tracing.load(str(sample)))}) is None
