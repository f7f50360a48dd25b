"""Benchmark harness — one section per paper table/figure + system benches.

Prints ``name,us_per_call,derived`` CSV rows:
  ladder        Table 1/2 + Fig 13/15 (optimization-ladder throughput)
  waitprob      Fig 14 (wait-for-flip probability vs vector width)
  fastexp       §2.4 + Fig 17 (exp approximation speed and error)
  rng           §3 (interlaced MT19937 throughput)
  kernels       Pallas kernel structural accounting + interpret timings
  serve         SampleServer packed vs sequential throughput
                (writes BENCH_serve.json)
  roofline      summary of the dry-run roofline table if present
  smoke         every SweepEngine (rung, backend) combination on a tiny
                model, correctness-only, <60 s — the CI gate

Run:  PYTHONPATH=src python -m benchmarks.run [section ...]
      PYTHONPATH=src python -m benchmarks.run --smoke
"""

from __future__ import annotations

import json
import os
import sys


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    args = sys.argv[1:]
    if "--smoke" in args:
        args = [a for a in args if a != "--smoke"] + ["smoke"]
    sections = args or [
        "ladder", "waitprob", "fastexp", "rng", "kernels", "serve", "roofline",
    ]
    rows = []
    failed = []
    for section in sections:
        print(f"# --- {section} ---", flush=True)
        try:
            if section == "ladder":
                from benchmarks import ladder

                rows += ladder.run()
            elif section == "waitprob":
                from benchmarks import waitprob

                rows += waitprob.run()
            elif section == "fastexp":
                from benchmarks import fastexp_bench

                rows += fastexp_bench.run()
            elif section == "rng":
                from benchmarks import rng_bench

                rows += rng_bench.run()
            elif section == "kernels":
                from benchmarks import kernel_bench

                rows += kernel_bench.run()
            elif section == "serve":
                from benchmarks import serve_bench

                rows += serve_bench.run()
            elif section == "smoke":
                from benchmarks import smoke

                rows += smoke.run()
            elif section == "roofline":
                path = os.path.join(os.path.dirname(__file__), "..", "dryrun_results.json")
                if os.path.exists(path):
                    from benchmarks import roofline

                    for r in roofline.run(path):
                        rows.append(
                            (f"roofline_{r['arch']}_{r['shape']}", 0.0,
                             f"dom={r['dominant']} frac={r['roofline_fraction']}")
                        )
                else:
                    rows.append(("roofline", 0.0, "dryrun_results.json not found - run launch.dryrun"))
            else:
                rows.append((section, 0.0, "unknown section"))
        except Exception as e:  # noqa: BLE001
            rows.append((section, 0.0, f"ERROR {type(e).__name__}: {e}"))
            failed.append(section)
        # stream rows as they come
        while rows:
            name, us, derived = rows.pop(0)
            print(f"{name},{us:.3f},{derived}", flush=True)
    if failed:
        # Keep streaming every section, but fail the process so CI gates
        # (smoke, bench-artifact steps) go red instead of printing an
        # ERROR row into a green build.
        sys.exit(f"benchmark sections failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
