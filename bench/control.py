"""The control of the check: the plain reference computed in bfloat16 (the
nearest precision below the configuration's float32) put in the
program's place, at a cell's own size, compared by the same check.

    python3 bench/control.py --workload aqua-pt.ladders --seeds 11 12 13 --sweeps 2000

The mix's job kind (``bench/kinds/<kind>.py``, its ``control``) replays
what a run would compare: for a ladder cell every ladder of one chip's
share of the seed's traffic for ``--sweeps`` sweeps; for an annealing
cell the first ``sample_jobs`` jobs of the seed's stream to their end
and ``sample_active`` more to half of their budget.
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
    kind = spec.kind(mix["kind"])
    for seed in args.seeds:
        t0 = time.perf_counter()
        insts = models.instances(cfg, seed)
        lat = reference.Lattice(insts[0]["n"], insts[0]["L"], LANES, insts[0]["space_nbr"])
        out = kind.control(lat, insts, mix, seed, jnp.bfloat16, args.sweeps)
        t1 = time.perf_counter()
        nums = check.numbers(out, lat, insts, 0, kind)
        ok, _ = check.verdict(nums, kind)
        print(json.dumps(dict(workload=args.workload, seed=seed, correct=ok, numbers=nums,
                              control_s=t1 - t0, reference_s=time.perf_counter() - t1)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
