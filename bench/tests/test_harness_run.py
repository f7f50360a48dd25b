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


def test_a_closed_set_whose_jobs_all_retired_is_still_served_and_compared(tiny_cell):
    """Ladders that retire before the close: a tick returns at once, and
    their results are compared against the reference."""
    from harness import cell, check, models, reference, spec

    c, cfg, mix = tiny_cell("ladders")
    mix.update(sweeps=32)
    kind = spec.kind(mix["kind"])
    insts = models.instances(cfg, 7)
    served = cell.Served(cfg, 1, insts, kind)
    loop = cell.Loop(served, mix, 1, 7)
    loop.start()
    for _ in range(16):
        loop.tick()
    assert len(loop.retired) == len(served.jobs) == 2
    loop.tick()  # nothing active, queued or due
    out = kind.outputs(served, loop, [], mix, None)
    lat = reference.Lattice(insts[0]["n"], insts[0]["L"], served.server.engine.V,
                            insts[0]["space_nbr"])
    res = check.compare(out, lat, insts, 0, kind)
    assert res["correct"] and res["compared"] == 8, res
