"""Record a traced window of a cell as a sample of the normalized record
(``bench/trace_sample/<config>.<traffic>.json.gz``), the trace the
reduction's tests read.

    python3 bench/record_trace.py --config aqua-pt --traffic ladders --seed 5

Runs the cell as ``bench/run.py --trace 1`` does, on the chips that JAX
finds, with a configuration and a traffic mix named directly, so a mix
that no cell of BENCHMARK.json uses yet can be recorded too.  It prints
the reduced numbers and the check's verdict, and needs a TPU.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--out", help="default: bench/trace_sample/<config>.<traffic>.json.gz")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH.parent / ".jax_cache")
    sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
    import jax

    jax.config.update("jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from harness import cell, spec, tracing

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 1
    cfg = spec._read(spec.config_path(args.config))
    mix = spec._read(spec.traffic_path(args.traffic))
    c = dict(name=f"{args.config}.{args.traffic}", config=args.config, traffic=args.traffic,
             chips=len(jax.devices()))
    rec = cell.run(c, cfg, mix, args.seed, args.seconds, True, T_PROCESS, cell.CompileCounter())
    out = args.out or str(BENCH / "trace_sample" / f"{c['name']}.json.gz")
    tracing.save(rec["trace_record"], out)
    t = rec["trace"]
    print(json.dumps(dict(out=out, correct=rec["checks"]["correct"], window_s=t["window_s"],
                          busy_s=t["busy_s"], kernel_s=t["kernel_s"], idle_gaps=t["idle_gaps"],
                          spans={k: [len(v), sum(v)] for k, v in t["spans"].items()},
                          idle_under=t["idle_under"], counters=t["counters"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
