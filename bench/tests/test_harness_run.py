"""Whole tiny runs of the harness on the CPU (a 6 x 8 lattice, the jnp
backend): counts, compiles and the check on a sound program."""

from __future__ import annotations

import pytest

CELLS = ["ladders", "anneal-backlog", "anneal-open"]


@pytest.mark.parametrize("name", CELLS)
def test_a_sound_run_is_correct_and_counts_its_work(tiny_run, name):
    rec = tiny_run(name)
    assert rec["checks"]["correct"], rec["checks"]
    assert rec["checks"]["compared"] > 0
    assert rec["spin_updates"] == rec["busy_slot_sweeps"] * rec["spins_per_replica"] > 0
    assert rec["compiles_window"] == 0
    assert rec["failed"] == 0


def test_open_loop_times_every_due_job(tiny_run):
    rec = tiny_run("anneal-open")
    assert rec["due"] and not rec["lost"]
    assert len(rec["latencies"]) == len(rec["due"])
    assert min(rec["latencies"]) > 0
