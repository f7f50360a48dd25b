"""The chip benchmark of the sampling service: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the
cell asks for.  It loads the cell's configuration and traffic mix by the
names in BENCHMARK.json, makes the instances and jobs from ``--seed``,
warms every program of the cell up, serves the mix for ``--seconds`` on
`SampleServer`'s own path, and then checks what the window produced
against the plain reference (bench/harness/reference.py).

It stops with a nonzero exit, and prints no result, unless JAX's devices
are TPUs and as many as the cell asks for.  Earlier lines of standard
output describe the run: the device, set-up, compiles inside the window,
the compile cache, and the numbers behind the metrics.  The numbers the
check compared, each beside its limit, are the last lines of standard
error.  The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``.  With ``--trace 0`` the metrics are
the cell's end-to-end metrics, with ``--trace 1`` its per-layer ones.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def log(msg: str) -> None:
    print(msg, flush=True)


def device_info(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def end_to_end(metrics: list, rec: dict) -> dict:
    values = {"setup_s": rec["setup_s"]}
    if rec["window_s"] > 0:
        values["spin_updates_per_s"] = rec["spin_updates"] / rec["window_s"]
        values["jobs_per_s"] = len(rec["retired_window"]) / rec["window_s"]
    lat = rec.get("latencies")
    if lat and len(lat) >= 20:
        values["job_latency_p95_s"] = statistics.quantiles(lat, n=20, method="inclusive")[-1]
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in metrics if m["name"] in values}


def per_layer(metrics: list, ctx: dict) -> dict:
    from harness import spec

    out = {}
    for m in metrics:
        v = spec.reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # The compile cache sits at one fixed path inside the checkout.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    from harness import spec

    bench = spec.load_benchmark(ROOT)
    cell, cfg, mix = spec.resolve(bench, args.workload)

    import jax

    jax.config.update("jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from harness import cell as cell_mod, peaks

    counter = cell_mod.CompileCounter()
    device = device_info(jax)
    log(f"device: platform={device['platform']} device_kind={device['kind']} "
        f"count={device['count']}")
    if device["platform"] != "tpu":
        print(f"bench: needs a TPU; JAX's first device is on {device['platform']!r}",
              file=sys.stderr)
        return 1
    if device["count"] < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} chips, found {device['count']}",
              file=sys.stderr)
        return 1
    chip_peaks = peaks.peaks_for(device["kind"])

    rec = cell_mod.run(cell, cfg, mix, args.seed, args.seconds, bool(args.trace),
                       T_PROCESS, counter)
    device["memory_peak_bytes"] = int(rec["memory_peak_bytes"])
    log(f"cell {args.workload}: config {cell['config']}, traffic {cell['traffic']}, "
        f"{cell['chips']} chip(s), seed {args.seed}")
    log(f"setup_s {rec['setup_s']!r}; compile cache {os.environ['JAX_COMPILATION_CACHE_DIR']}: "
        f"{counter.counts['cache_hits']} hits, {counter.counts['cache_misses']} misses")
    log(f"compiles inside the window: {rec['compiles_window']} requests, "
        f"{rec['backend_compiles_window']} backend compiles")
    log(f"window {rec['window_s']!r} s: {rec['spin_updates']} spin updates for jobs, "
        f"busy slot-sweeps {rec['busy_slot_sweeps']} x {rec['spins_per_replica']} spins, "
        f"{len(rec['retired_window'])} jobs retired")
    log(f"busy slot-sweeps launched in each fifth of the window: {rec['fifths']}")
    if "latencies" in rec:
        lat, late = rec["latencies"], rec["lateness"]
        if lat:
            log(f"open loop: {len(rec['due'])} jobs due, {len(lat)} retired, "
                f"{len(rec['lost'])} lost; latency median {statistics.median(lat)!r} s, "
                f"max {max(lat)!r} s")
        if late:
            log(f"generator lateness: median {statistics.median(late)!r} s, max {max(late)!r} s")
    log(f"reference and comparison took {rec['reference_s']!r} s; "
        f"{rec['checks']['compared']} replicas or jobs compared")

    result = {"correct": bool(rec["checks"]["correct"]), "attempted": int(rec["attempted"]),
              "failed": int(rec["failed"])}
    if args.trace:
        t = rec["trace"]
        ctx = dict(trace=t, peaks=chip_peaks, spins_per_replica=rec["spins_per_replica"],
                   device_kind=device["kind"])
        result["metrics"] = per_layer(spec.metrics_of(bench, args.workload, "per_layer"), ctx)
        device["busy_s"] = t["busy_s"]
        device["window_s"] = t["window_s"]
        result["breakdown"] = {"device_ops": t["device_ops"], "idle_gaps": t["idle_gaps"]}
        updates = t["counters"].get("busy_slot_sweeps", 0) * rec["spins_per_replica"]
        if updates and t["kernel_s"] > 0:
            log(f"sweep kernel: {t['kernel_s'] / t['devices']!r} s of device time per chip in a "
                f"{t['window_s']!r} s traced window, {updates} spin updates; "
                f"{t['kernel_s'] / updates * 1e9!r} ns of kernel time per spin update "
                f"on one chip; roofline against HBM alone ({chip_peaks['source']}), "
                "no f32 vector peak is published")
        for name, spans in t["spans"].items():
            if spans:
                log(f"span {name}: {len(spans)} x {sum(spans) / len(spans) * 1e3!r} ms, "
                    f"device idle under it {t['idle_under'][name]!r} s")
        log(f"traced window: {t['counters']}; longest idle gap {t['longest_gap_s']!r} s")
    else:
        result["metrics"] = end_to_end(spec.metrics_of(bench, args.workload, "end_to_end"), rec)
    result["device"] = device
    result["checks"] = rec["checks"]["checks"]
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
