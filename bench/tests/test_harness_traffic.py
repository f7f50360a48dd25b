"""The traffic generator: deterministic for a seed, and the budgets,
schedules, users and rates its mix files state."""

from __future__ import annotations

import json

import numpy as np

from harness import spec

SEED = 2**31 + 777


def _mix(name, **changes):
    with open(spec.traffic_path(name)) as f:
        return dict(json.load(f), **changes)


def _stream(mix, instances, seed):
    return spec.kind(mix["kind"]).specs(mix, 1, seed, instances)


def _ladders(mix, chips, seed):
    return spec.kind(mix["kind"]).specs(mix, chips, seed, 1)


OPEN = dict(loop="open", rate_per_s=5.0, gap_block=256)


def test_anneal_stream_is_deterministic_per_seed():
    mix = _mix("anneal-backlog", **OPEN)
    a = _stream(mix, 64, SEED).take(200)
    b = _stream(mix, 64, SEED).take(200)
    c = _stream(mix, 64, SEED + 1).take(200)
    key = lambda s: (s["seed"], s["user"], s["instance"], tuple(s["schedule"]), s["arrival"])
    assert [key(s) for s in a] == [key(s) for s in b]
    assert [key(s) for s in a] != [key(s) for s in c]


def test_every_seed_gets_the_same_budgets_in_another_order():
    mix = _mix("anneal-backlog")
    allowed = list(range(512, 1537, 32))
    per_seed = []
    for seed in (1, SEED):
        specs = _stream(mix, 64, seed).take(len(allowed))
        per_seed.append([sum(s for s, _ in x["schedule"]) for x in specs])
        assert sorted(per_seed[-1]) == allowed
    assert per_seed[0] != per_seed[1]


def test_ramps_are_linear_in_steps_of_32_sweeps():
    mix = _mix("anneal-backlog")
    for s in _stream(mix, 64, SEED).take(50):
        sweeps = [k for k, _ in s["schedule"]]
        betas = np.array([b for _, b in s["schedule"]])
        assert set(sweeps) == {32}
        assert betas[0] == 0.1 and abs(betas[-1] - 3.0) < 1e-12
        np.testing.assert_allclose(np.diff(betas), (3.0 - 0.1) / (len(betas) - 1))


def test_users_are_zipf_skewed_and_own_their_instances():
    mix = _mix("anneal-backlog")
    specs = _stream(mix, 64, SEED).take(4000)
    counts = np.bincount([int(s["user"][4:]) for s in specs], minlength=32)
    assert counts[0] > counts[1] > counts[3] > counts[15]
    assert counts[0] / len(specs) > 0.15
    assert all(s["instance"] // 2 == int(s["user"][4:]) for s in specs)


def test_open_arrivals_come_at_the_stated_rate():
    mix = _mix("anneal-backlog", **OPEN)
    block = mix["gap_block"]
    specs = _stream(mix, 64, SEED).take(4 * block)
    t = np.array([s["arrival"] for s in specs])
    assert np.all(np.diff(t) > 0)
    rate = block / (t[block - 1])
    np.testing.assert_allclose(rate, mix["rate_per_s"], rtol=1e-9)
    np.testing.assert_allclose(4 * block / t[-1], mix["rate_per_s"], rtol=1e-9)


def test_closed_mixes_have_no_arrival_times():
    mix = _mix("anneal-backlog")
    assert all(s["arrival"] is None for s in _stream(mix, 64, SEED).take(10))


def test_ladders_are_the_papers_work_unit():
    mix = _mix("ladders")
    for chips in (1, 4):
        specs = _ladders(mix, chips, SEED)
        assert len(specs) == 4 * chips
        assert len({s["seed"] for s in specs}) == len(specs)
        for s in specs:
            assert len(s["betas"]) == 115 and s["rounds"] * s["sweeps_per_round"] == 30000
            np.testing.assert_allclose(s["betas"], np.geomspace(0.1, 3.0, 115), rtol=1e-6)
    assert [s["seed"] for s in _ladders(mix, 1, SEED)] == \
        [s["seed"] for s in _ladders(mix, 1, SEED)]
