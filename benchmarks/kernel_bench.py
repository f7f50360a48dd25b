"""Pallas kernel benchmarks + structural VMEM accounting (TPU target).

Wall times below run the kernels in interpret mode on CPU — meaningful
only as correctness-path checks and for *relative* launch-structure
comparisons; the perf-relevant output is the structural accounting: the
VMEM one replica adds to a kernel tile at the paper's shape, and how many
replicas the tile budget holds (the kernel's own arithmetic,
`metropolis_kernel.replica_vmem_bytes`).

The headline comparison (`launch_structure_compare`) times the two sweep
launch structures the engine can dispatch to, at replica batches
B in {1, 8, 115} (115 = the paper's production replica count):

  per-sweep path   one `pallas_call` per sweep, uniforms generated
                   host-side by the interlaced MT19937 and shipped in
                   (the seed architecture).
  fused path       ONE `pallas_call` advancing num_sweeps x B
                   replica-sweeps with the MT19937 twist/temper fused
                   into the kernel body (no host round-trips).

Reported as us/sweep (whole batch advanced one sweep).

`colored_vs_sequential` is the sweep-ORDER comparison at the paper's
production shape: the graph-colored "cb" rung (C ~ 4 whole-lattice vector
updates per sweep) vs the sequential a4 rung (rows serial row steps per
sweep), on both backends, written to BENCH_kernel.json.
"""

from __future__ import annotations

import numpy as np

from benchmarks.common import time_fn, write_bench_json
from repro.configs.ising_qmc import CONFIG as PAPER
from repro.core import ising, mt19937 as mt
from repro.core.engine import SweepEngine
from repro.kernels import metropolis_kernel as mk
from repro.kernels import ops

LANES = 128


def launch_structure_compare(
    batches=(1, 8, 115), num_sweeps: int = 8, n: int = 4, L: int = 256
):
    """Fused multi-sweep single-launch vs one-launch-per-sweep + host RNG.

    The per-sweep baseline is jitted end-to-end (one cached callable, like
    the fused path) so the comparison isolates launch structure and host
    RNG round-trips, not Python dispatch overhead.
    """
    import jax

    m = ising.random_layered_model(n=n, L=L, seed=1, beta=1.0)
    rows_out = []
    records = []
    for B in batches:
        eng = SweepEngine.build(m, rung="a4", backend="pallas", batch=B, V=LANES)
        carry = eng.init_carry(seed=0)
        fused_fn = eng.run_fn(num_sweeps)
        dt_fused, _ = time_fn(fused_fn, carry, iters=5, warmup=1)

        # Seed architecture: host-side bulk RNG + one kernel launch per sweep.
        nbr, J2, tau2 = (
            eng.tables["base_nbr"], eng.tables["base_J2"], eng.tables["tau_J2"],
        )
        rows = eng.rows

        @jax.jit
        def per_sweep_path(c):
            state = (c.spins, c.h_space, c.h_tau)
            rng = c.rng
            for _ in range(num_sweeps):
                rng, u = mt.mt_uniforms_count(rng, rows)
                u = u.reshape(rows, B, LANES).transpose(1, 0, 2)
                state = ops.metropolis_sweep(
                    *state, u, nbr, J2, tau2, c.betas, n=m.n
                )
            return state

        dt_seed, _ = time_fn(per_sweep_path, carry, iters=5, warmup=1)
        us_f = dt_fused / num_sweeps * 1e6
        us_s = dt_seed / num_sweeps * 1e6
        rows_out.append(
            (f"kernel_fused_B{B}_us_per_sweep", us_f,
             f"{us_f:.0f}us vs per-sweep {us_s:.0f}us = {dt_seed/dt_fused:.2f}x "
             "(interpret mode)")
        )
        rows_out.append((f"kernel_persweep_B{B}_us_per_sweep", us_s, ""))
        records.append(
            {
                "name": f"kernel_fused_B{B}",
                "B": B,
                "sweeps_per_sec": num_sweeps / dt_fused,
                "wall_clock_s": dt_fused,
                "speedup_vs_persweep": dt_seed / dt_fused,
                "mode": "interpret",
            }
        )
        records.append(
            {
                "name": f"kernel_persweep_B{B}",
                "B": B,
                "sweeps_per_sec": num_sweeps / dt_seed,
                "wall_clock_s": dt_seed,
                "mode": "interpret",
            }
        )
    return rows_out, records


def colored_vs_sequential(B: int = 8, num_sweeps: int = 2):
    """The colored-rung headline: "cb" vs "a4" at the PAPER production
    shape (96 spins x 256 layers -> rows=192), both backends, B replicas.

    The sequential a4 sweep is `rows` serial row steps per sweep however
    wide the hardware is; the colored sweep is C ~ 4 whole-lattice vector
    updates.  Interpret-mode wall clock exaggerates a4's per-op dispatch
    cost, but the structural point — O(rows) serial steps vs O(C) vector
    steps — is exactly what a real TPU build hits as well.
    """
    m = ising.random_layered_model(
        n=PAPER.spins_per_layer, L=PAPER.num_layers, seed=1, beta=1.0
    )
    rows_out, records = [], []
    sweeps_per_sec = {}
    for backend in ("pallas", "jnp"):
        for rung in ("a4", "cb"):
            eng = SweepEngine.build(m, rung=rung, backend=backend, batch=B, V=LANES)
            carry = eng.init_carry(seed=0)
            dt, _ = time_fn(eng.run_fn(num_sweeps), carry, iters=3, warmup=1)
            sps = num_sweeps / dt
            sweeps_per_sec[(rung, backend)] = sps
            name = f"kernel_{rung}_{backend}_paper_B{B}"
            rows_out.append(
                (f"{name}_us_per_sweep", dt / num_sweeps * 1e6,
                 f"{sps:.1f} sweeps/s (interpret mode)" if backend == "pallas"
                 else f"{sps:.1f} sweeps/s")
            )
            records.append(
                {
                    "name": name,
                    "B": B,
                    "sweeps_per_sec": sps,
                    "wall_clock_s": dt,
                    "rung": rung,
                    "backend": backend,
                    "mode": "interpret" if backend == "pallas" else "jnp",
                }
            )
    for backend in ("pallas", "jnp"):
        speedup = sweeps_per_sec[("cb", backend)] / sweeps_per_sec[("a4", backend)]
        rows_out.append(
            (f"kernel_cb_vs_a4_{backend}_paper_speedup", speedup, f"{speedup:.1f}x")
        )
        for r in records:
            if r["rung"] == "cb" and r["backend"] == backend:
                r["speedup_vs_a4"] = speedup
    return rows_out, records


def run():
    rows_out = []
    # Paper production shape: 256 layers x 96 spins, a4 kernel.
    paper = ising.random_layered_model(
        n=PAPER.spins_per_layer, L=PAPER.num_layers, seed=1
    )
    rows = paper.n * paper.L // LANES
    per = mk.replica_vmem_bytes(
        rows, paper.n, paper.space_degree + 1, fields_in=True, multi=False
    )
    budget = mk.TILE_VMEM_BUDGET
    rows_out.append(
        ("kernel_vmem_paper_shape", 0.0,
         f"{per/1024:.0f}KiB per replica, double-buffered, of a "
         f"{budget >> 20}MiB tile budget ({per/budget:.1%}) rows={rows}")
    )
    rows_out.append(
        ("kernel_vmem_max_replicas_resident", 0.0, f"{budget // per}")
    )
    # Launch-structure comparison: fused multi-sweep vs seed per-sweep path.
    compare_rows, records = launch_structure_compare()
    rows_out += compare_rows
    # Colored-vs-sequential sweep order at the paper production shape.
    colored_rows, colored_records = colored_vs_sequential()
    rows_out += colored_rows
    records += colored_records
    rows_out.append(("kernel_bench_json", 0.0, write_bench_json("kernel", records)))
    # interpret-mode correctness-path timing (small shape).
    m = ising.random_layered_model(n=4, L=256, seed=1, beta=1.0)
    inputs = ops.make_kernel_inputs(m, batch=1, seed=0)
    dt, _ = time_fn(lambda: ops.metropolis_sweep(*inputs, n=m.n), iters=2, warmup=1)
    rows_out.append(
        ("kernel_sweep_interpret_ms", dt * 1e6, f"{dt*1e3:.1f}ms (interpret mode)")
    )
    st = mt.mt_init(np.arange(128, dtype=np.uint32))
    dt, out = time_fn(lambda: ops.mt_next_block(st), iters=3, warmup=1)
    rows_out.append(
        ("kernel_mt19937_interpret", dt * 1e6,
         f"{out[1].size/dt/1e6:.2f}Mrand/s (interpret mode)")
    )
    return rows_out


if __name__ == "__main__":
    for r in run():
        print(",".join(str(x) for x in r))
