"""Simulated quantum annealing — the paper's production context (AQUA@Home).

Path-integral QMC of a transverse-field Ising problem: the transverse field
Gamma anneals down while the layered classical model (L Trotter slices) is
swept with the vectorized Metropolis kernel.  Each anneal is one `SQAJob`
on a multi-tenant `SampleServer`: one slot, one seed, one generator stream
for the whole ramp, and its slot's tau couplings rewritten at every Gamma
step.  The final layer-majority state is the annealer's answer; we
compare its problem energy against random assignments.

  PYTHONPATH=src python examples/quantum_annealing.py
"""

import numpy as np

from repro.core import ising, qmc
from repro.serve_mc import SampleServer, SQAJob


def main():
    # The problem: its fields and couplings, over L=32 Trotter slices (an
    # SQAJob ignores the model's own tau couplings and beta).
    problem = ising.random_layered_model(n=24, L=32, seed=7)
    pb = qmc.QMCProblem.of(problem)
    beta, steps, sweeps = 2.0, 12, 4
    gammas = [g for _, g in qmc.anneal_schedule(steps, beta=beta)]
    server = SampleServer(problem, slots=4, multi_tenant=True, rung="a4",
                          backend="jnp", V=4, chunk_sweeps=sweeps)
    for seed in range(4):
        server.submit(SQAJob(seed, beta, gammas, sweeps, model=problem))

    print(f"annealing Gamma 3.0 -> 0.05 over {steps} steps, {sweeps} sweeps each, "
          "4 seeds in one server")
    results = sorted(server.drain(), key=lambda r: r.jid)
    for r in results:
        print(f"  job {r.jid}: layered energy {r.energy:9.2f} at Gamma "
              f"{r.extras['final_gamma']:.2f}, problem energy "
              f"{r.extras['problem_energy']:.2f}")
    print(f"  {server.telemetry.value('engine.table_rewrites')} tau rewrites for "
          f"{server.launches} launches")

    e_anneal = min(r.extras["problem_energy"] for r in results)
    rng = np.random.default_rng(0)
    e_random = np.mean([
        pb.energy(rng.choice([-1.0, 1.0], size=pb.h.shape[0]))
        for _ in range(200)
    ])
    print(f"problem energy: annealed {e_anneal:.2f} vs random mean {e_random:.2f}")
    assert e_anneal < e_random, "annealing should beat random assignment"
    print("OK: annealed state beats random baseline")


if __name__ == "__main__":
    main()
