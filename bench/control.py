"""The control of the check: the plain reference computed in bfloat16 (the
nearest precision below the configuration's float32) put in the
program's place, at a cell's own size, compared by the same check.

    python3 bench/control.py --workload aqua-pt.ladders --seeds 11 12 13 --sweeps 2000

For a ladder cell it replays every ladder of one chip's share of the
seed's traffic for ``--sweeps`` sweeps, as a run compares them all; for
an annealing cell it replays the first ``sample_jobs`` jobs of the
seed's stream to their end and ``sample_active`` more to half of their
budget, as a run samples them.
Each seed prints one JSON line of the compared numbers, with the
verdict, which has to be false.  The benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
LANES = 128  # the Pallas backend's lane count, which the served engine uses


def ladder_outputs(lat, insts, mix, seed, sweeps, ftype):
    """Every ladder of one chip's share of the seed's traffic, replayed for
    ``sweeps`` sweeps in ``ftype``, in the shape a run's outputs have."""
    from harness import reference, traffic

    specs = traffic.ladder_specs(mix, 1, seed)
    refs = reference.replay_ladders(lat, insts[0], [(s["seed"], s["betas"]) for s in specs],
                                    sweeps, specs[0]["sweeps_per_round"], ftype=ftype)
    out = []
    for spec, ref in zip(specs, refs):
        flat = reference.from_lane(ref["spins"], lat.n, lat.L, lat.V)
        out.append(dict(
            kind="pt", seed=spec["seed"], betas0=spec["betas"], sweeps=sweeps,
            sweeps_per_round=spec["sweeps_per_round"], spins=ref["spins"],
            h_space=ref["h_space"], h_tau=ref["h_tau"], rng=ref["rng"], betas=ref["betas"],
            spins_flat=flat, energy=reference.energy_f64(insts[0], flat),
            swap_accept=ref["swap_accept"], swap_propose=ref["swap_propose"]))
    return dict(ladders=out)


def anneal_outputs(lat, insts, mix, seed, ftype):
    from harness import reference, traffic

    stream = traffic.AnnealStream(mix, len(insts), seed)
    done_specs = stream.take(mix["sample_jobs"])
    live_specs = stream.take(mix["sample_active"])
    jobs = [dict(spec=s, sweeps=sum(k for k, _ in s["schedule"])) for s in done_specs]
    jobs += [dict(spec=s, sweeps=sum(k for k, _ in s["schedule"]) // 16 * 8) for s in live_specs]
    refs = reference.replay_anneals(lat, [
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sweeps", type=int, default=2000, help="ladder sweeps to replay")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH)]
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(BENCH.parent / ".jax_cache"))
    import jax.numpy as jnp

    from harness import check, models, reference, spec

    cell, cfg, mix = spec.resolve(spec.load_benchmark(), args.workload)
    for seed in args.seeds:
        t0 = time.perf_counter()
        insts = models.instances(cfg, seed)
        lat = reference.Lattice(insts[0]["n"], insts[0]["L"], LANES, insts[0]["space_nbr"])
        if mix["kind"] == "pt_ladders":
            out = ladder_outputs(lat, insts, mix, seed, args.sweeps, jnp.bfloat16)
        else:
            out = anneal_outputs(lat, insts, mix, seed, jnp.bfloat16)
        t1 = time.perf_counter()
        nums = check.numbers(out, lat, insts, 0)
        ok, _ = check.verdict(nums)
        print(json.dumps(dict(workload=args.workload, seed=seed, correct=ok, numbers=nums,
                              control_s=t1 - t0, reference_s=time.perf_counter() - t1)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
