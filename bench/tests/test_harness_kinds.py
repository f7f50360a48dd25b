"""Job kinds are modules the harness finds by the traffic file's
``kind`` (``bench/kinds/<kind>.py``): a kind added as a file alone is
served, and the two kinds give the specs, control numbers and check
numbers recorded with the harness as it was before the kinds became
modules (``recorded_values.json``)."""

from __future__ import annotations

import hashlib
import json
import textwrap
from pathlib import Path

import numpy as np
import pytest

from harness import check, models, reference, spec

HERE = Path(__file__).resolve().parent
RECORDED = json.loads((HERE / "recorded_values.json").read_text())
SEEDS = [3, 2**31 + 5, 2**31 + 12345]
KIND_NAMES = ('"pt"', '"anneal"', "pt_ladders", "anneal_ramps", "PTJob", "AnnealJob")


def _plain(x):
    if isinstance(x, np.ndarray):
        return [float(v) for v in x.tolist()]
    if isinstance(x, np.floating):
        return float(x)
    if isinstance(x, np.integer):
        return int(x)
    if isinstance(x, dict):
        return {k: _plain(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_plain(v) for v in x]
    return x


def _digest(specs) -> str:
    return hashlib.sha256(json.dumps(_plain(specs), sort_keys=True).encode()).hexdigest()


def _mix(name, **changes):
    return dict(spec._read(spec.traffic_path(name)), **changes)


@pytest.mark.parametrize("seed", SEEDS)
def test_spec_streams_equal_the_recorded(seed):
    ladders, ramps = spec.kind("pt_ladders"), spec.kind("anneal_ramps")
    lad = _mix("ladders")
    got = {
        "ladders.1": _digest(ladders.specs(lad, 1, seed, 1)),
        "ladders.4": _digest(ladders.specs(lad, 4, seed, 1)),
        "anneal-backlog": _digest(ramps.specs(_mix("anneal-backlog"), 1, seed, 64).take(300)),
        "anneal-open": _digest(ramps.specs(
            _mix("anneal-backlog", loop="open", rate_per_s=5.0, gap_block=256), 1, seed, 64).take(300)),
    }
    assert got == RECORDED[str(seed)]["specs"]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", ["ladders", "anneal-backlog"])
def test_control_numbers_equal_the_recorded(tiny_cell, name, seed):
    c, cfg, mix = tiny_cell(name)
    if name != "ladders":
        mix.update(sample_jobs=12, sample_active=4)
    kind = spec.kind(mix["kind"])
    insts = models.instances(cfg, seed)
    lat = reference.Lattice(insts[0]["n"], insts[0]["L"], 4, insts[0]["space_nbr"])
    for label, ftype in (("bfloat16", reference.jnp.bfloat16), ("float32", reference.f32)):
        nums = check.numbers(kind.control(lat, insts, mix, seed, ftype, 256), lat, insts, 0, kind)
        assert nums == RECORDED[str(seed)]["control"][f"{name}.{label}"], label


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", ["ladders", "anneal-backlog", "anneal-open"])
def test_tiny_run_numbers_equal_the_recorded(tiny_run, name, seed):
    rec = tiny_run(name, seed=seed)
    got = {k: v["value"] for k, v in rec["checks"]["checks"].items()}
    got["correct"] = rec["checks"]["correct"]
    assert got == RECORDED[str(seed)]["runs"][name]


# A kind of its own: a closed batch of single-slot ramps, all submitted at
# once and every one compared, with a number of its own.
BATCH_KIND = '''
    from harness import spec, traffic

    ramps = spec.kind("anneal_ramps")
    make_job, control = ramps.make_job, ramps.control
    LIMITS = {"jobs_unseen": (0, "every job of the batch is compared")}


    def specs(mix, chips, seed, instances):
        return traffic.Stream(mix, instances, seed, ramps.ramp).take(mix["jobs"])


    def outputs(served, loop, pool, mix, rng):
        every = dict(mix, sample_jobs=len(loop.retired), sample_active=len(served.jobs))
        return dict(ramps.outputs(served, loop, sorted(loop.retired), every, rng),
                    submitted=len(served.jobs))


    def numbers(out, outputs, lat, insts, ftype):
        ramps.numbers(out, outputs, lat, insts, ftype)
        out["jobs_unseen"] = outputs["submitted"] - out["compared"]
'''


def test_a_kind_added_as_a_file_is_found_and_served(tmp_path, monkeypatch, tiny_cell, cell_run):
    (tmp_path / "anneal_batch.py").write_text(textwrap.dedent(BATCH_KIND))
    found = spec.kind_path
    monkeypatch.setattr(spec, "kind_path", lambda name: (
        tmp_path / f"{name}.py" if (tmp_path / f"{name}.py").exists() else found(name)))
    c, cfg, mix = tiny_cell("anneal-backlog")
    mix.update(kind="anneal_batch", jobs=6, warmup_sweeps=16)
    rec = cell_run(c, cfg, mix)
    assert rec["checks"]["correct"], rec["checks"]
    assert rec["checks"]["compared"] == rec["attempted"] == 6
    assert rec["checks"]["checks"]["jobs_unseen"] == {"value": 0, "limit": 0}


def test_a_kind_may_not_change_a_shared_limit():
    class Kind:
        LIMITS = {"spins_differ": (5, "looser")}

    with pytest.raises(ValueError, match="shared limit"):
        check.limits(Kind)


@pytest.mark.parametrize("module", ["cell.py", "check.py", "traffic.py"])
def test_the_harness_names_no_job_kind(module):
    text = (spec.BENCH_DIR / "harness" / module).read_text()
    assert not [name for name in KIND_NAMES if name in text]
