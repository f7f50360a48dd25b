"""Smoke run of the sampling service on a TPU, at the paper's shape.

    python chip_smoke.py               # one chip: the phases below
    python chip_smoke.py --devices 4   # four chips: the mesh phase only

It drives the service through the entry points a user calls --
`SampleServer` over `SweepEngine.create`, with ``rung``, ``backend``,
``multi_tenant`` and ``mesh`` set as users set them -- at the full width
of the AQUA@Home deployment in `configs/ising_qmc.py`: 96 spins x 256
layers per replica, V=128 lanes, 32 replica slots.  Couplings are random,
made from ``--seed``.  The job mix is 8 `AnnealJob`s (constant and ramp)
and one 16-rung `PTJob` over beta 0.1..3.0, drained to the end.

Phases, in one process on one chip:

1. cb rung, Pallas backend.
2. cb rung, jnp backend at V=128: every job's spins, energy, fields, betas
   and MT19937 state must equal phase 1's bit for bit.
3. a4 rung, Pallas against jnp, with fewer sweeps.
4. Multi-tenant (each job samples its own `ising.reseed_couplings`
   model), cb and a4, Pallas against jnp: the two ``_multi`` kernels.
5. Reference, for every job of every phase: the energy against
   `ising.energy` on the host, the carried fields against
   `ising.h_eff_from_scratch` to f32 rounding.

With ``--devices 4`` it serves the cb mix on ``make_slot_mesh(4)`` and on
one device, requires every job bit-identical between the two (the f32
energies the devices sum for PT swaps included), and checks that the
slot pool's shards sit on four distinct devices.

The program stops with a nonzero exit, before printing any result, unless
JAX's first device is a TPU.  Lines before the last are chip output: the
device, and per phase the compile time, the wall time and whether the
Pallas kernel was compiled.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import collections
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SLOTS = 32
LANES = 128
PT_RUNGS = 16
NUM_ANNEALS = 8
# Sweeps per chunk; every budget below is a multiple of it, so each
# server compiles one chunk size.
CHUNK = {"cb": 16, "a4": 8}


def log(msg: str) -> None:
    print(msg, flush=True)


def job_mix(m, rung: str, models=None) -> list:
    """8 anneals (constant and 4-step ramps, 4..16 chunks of sweeps) and
    one 16-rung PT ladder; with ``models``, job i samples models[i]."""
    from repro.serve_mc import AnnealJob, PTJob

    class Captured:
        """Keeps the job's final slot state (spins, fields, betas, RNG)."""

        def finalize(self, server, slots):
            result = super().finalize(server, slots)
            result.extras["slot_state"] = [
                [np.asarray(x) for x in server.engine.extract_slot(server.carry, b)]
                for b in slots
            ]
            # The same energies summed on the device, in f32.
            energies = server.engine.slot_energies(server.carry)
            result.extras["device_energy"] = np.asarray(energies)[list(slots)]
            result.extras["model"] = self.model_on(server)
            return result

    class Anneal(Captured, AnnealJob):
        pass

    class PT(Captured, PTJob):
        pass

    c = CHUNK[rung]
    pick = (lambda i: None) if models is None else (lambda i: models[i % len(models)])
    jobs = []
    for i in range(NUM_ANNEALS):
        chunks = 4 + 12 * i // (NUM_ANNEALS - 1)
        if i % 2:
            jobs.append(Anneal.ramp(seed=100 + i, beta_start=0.1, beta_end=3.0,
                                    steps=4, sweeps_per_step=chunks // 4 * c,
                                    model=pick(i)))
        else:
            jobs.append(Anneal.constant(seed=100 + i, sweeps=chunks * c,
                                        beta=0.1 + 0.4 * i, model=pick(i)))
    jobs.append(PT(seed=200, betas=np.geomspace(0.1, 3.0, PT_RUNGS),
                   num_rounds=8, sweeps_per_round=c, model=pick(NUM_ANNEALS)))
    return jobs


def kernel_compiled(srv) -> bool:
    """Whether the server's sweep launch holds a compiled Pallas kernel
    (in interpret mode the kernel body lowers to plain XLA ops)."""
    import jax

    run = jax.jit(srv.engine.run_fn(srv.chunk_sweeps))
    return "tpu_custom_call" in run.lower(srv.carry).as_text()


def serve(m, rung: str, backend: str, *, models=None, mesh=None) -> dict:
    """Drain the mix through one `SampleServer`; returns the results by
    jid and the server."""
    from repro.serve_mc import SampleServer

    srv = SampleServer(m, slots=SLOTS, chunk_sweeps=CHUNK[rung], rung=rung,
                       backend=backend, V=LANES, multi_tenant=models is not None,
                       mesh=mesh)
    kernel = kernel_compiled(srv)
    if kernel != (backend == "pallas"):
        raise AssertionError(f"{rung}/{backend}: compiled Pallas kernel {kernel}")
    jobs = job_mix(m, rung, models)
    t0 = time.perf_counter()
    for job in jobs:
        srv.submit(job)
    results = {r.jid: r for r in srv.drain()}
    wall = time.perf_counter() - t0
    if len(results) != len(jobs):
        raise AssertionError(f"{rung}/{backend}: {len(results)} of {len(jobs)} jobs")
    first = srv.telemetry.histogram("serve.launch_s", phase="compile").snapshot()
    mode = ("Pallas kernel compiled (not interpret)" if kernel
            else "jnp, no Pallas kernel")
    tenancy = "multi-tenant" if models is not None else "single-model"
    devices = f" on {srv.devices} devices" if mesh is not None else ""
    log(f"phase {rung}/{backend} {tenancy}{devices}: first launch (compile) "
        f"{first['sum']:.2f}s, drain {wall:.2f}s for {len(jobs)} jobs in "
        f"{srv.launches} launches; {mode}")
    return {"results": results, "server": srv}


def check_reference(label: str, results: dict) -> None:
    """For every replica of every job: the reported energy (host, f64) and
    the device's f32 energy against `ising.energy`, the carried fields
    against `ising.h_eff_from_scratch`."""
    from repro.core import ising, reorder

    worst = {"host": 0.0, "device": 0.0, "fields": 0.0}
    for r in results.values():
        m = r.extras["model"]
        spins = np.atleast_2d(r.spins)
        reported = np.atleast_1d(r.energy)
        for k, (s, state) in enumerate(zip(spins, r.extras["slot_state"])):
            if not np.all(np.abs(s) == 1.0):
                raise AssertionError(f"{label}: job {r.jid} spins not +-1")
            e_ref = ising.energy(m, s)
            for key, e in (("host", reported[k]), ("device", r.extras["device_energy"][k])):
                err = abs(float(e) - e_ref) / max(1.0, abs(e_ref))
                worst[key] = max(worst[key], err)
            hs_ref, ht_ref = ising.h_eff_from_scratch(m, s)
            for got, ref in ((state[1], hs_ref), (state[2], ht_ref)):
                flat = reorder.from_lane(got[0], m.n, m.L, LANES)
                worst["fields"] = max(worst["fields"], float(np.max(np.abs(flat - ref))))
    if worst["host"] > 1e-9 or worst["device"] > 1e-4 or worst["fields"] > 2e-4:
        raise AssertionError(f"{label}: worst errors {worst}")
    log(f"reference {label}: {len(results)} jobs; energy vs ising.energy: "
        f"reported rel err {worst['host']:.3g}, device f32 rel err "
        f"{worst['device']:.3g}; fields vs h_eff_from_scratch abs err "
        f"{worst['fields']:.3g}")


FIELDS = ("spins", "h_space", "h_tau", "betas", "rng")


def check_bit_equal(label: str, ref: dict, got: dict) -> None:
    """Every job's result and final slot state identical to the bit."""
    assert ref.keys() == got.keys(), (label, ref.keys(), got.keys())
    for jid, a in ref.items():
        b = got[jid]
        pairs = [("spins", a.spins, b.spins),
                 ("energy", np.asarray(a.energy), np.asarray(b.energy)),
                 ("device energy", a.extras["device_energy"], b.extras["device_energy"])]
        for k, (sa, sb) in enumerate(zip(a.extras["slot_state"], b.extras["slot_state"])):
            pairs += [(f"replica {k} {f}", x, y) for f, x, y in zip(FIELDS, sa, sb)]
        for name, x, y in pairs:
            if x.dtype != y.dtype or not np.array_equal(x, y):
                raise AssertionError(f"{label}: job {jid} {name} differs")
    log(f"bit-exact {label}: {len(ref)} jobs, spins, host and device energies, "
        "fields, betas and MT19937 state equal")


def single_chip_phases(m, seed: int) -> None:
    from repro.core import ising

    tenants = [ising.reseed_couplings(m, seed + k) for k in range(1, 5)]
    for models in (None, tenants):
        for rung in ("cb", "a4"):
            tenancy = "multi-tenant" if models else "single-model"
            pallas = serve(m, rung, "pallas", models=models)["results"]
            jnp_ = serve(m, rung, "jnp", models=models)["results"]
            check_bit_equal(f"{rung} {tenancy} jnp vs pallas", pallas, jnp_)
            check_reference(f"{rung} {tenancy} pallas", pallas)


def mesh_phase(m, devices: int) -> None:
    from repro.launch.mesh import make_slot_mesh

    ref = serve(m, "cb", "pallas")["results"]
    sharded = serve(m, "cb", "pallas", mesh=make_slot_mesh(devices))
    srv = sharded["server"]
    shard_devices = {s.device for s in srv.carry.spins.addressable_shards}
    ready = srv.engine.device_ready_times(srv.carry, time.perf_counter())
    if len(shard_devices) != devices or len(ready) != devices:
        raise AssertionError(f"slot pool on {len(shard_devices)} devices, "
                             f"{len(ready)} ready times; expected {devices}")
    place = srv.stats()["placement"]
    log(f"mesh: slot pool shards on devices "
        f"{sorted(d.id for d in shard_devices)}, device_ready_times "
        f"{[round(float(t), 6) for t in ready]}; PT swaps "
        f"{place['pt_swap_cross']} cross-device, {place['pt_swap_local']} local")
    check_bit_equal(f"cb pallas D={devices} vs D=1", ref, sharded["results"])
    check_reference(f"cb pallas D={devices}", sharded["results"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--devices", type=int, default=1, choices=(1, 4),
                    help="4: serve over a four-chip mesh against one chip, "
                         "and run no other phase")
    ap.add_argument("--seed", type=int, default=0, help="seed of the couplings")
    args = ap.parse_args(argv)

    import jax
    import jax.monitoring

    from repro.configs.ising_qmc import CONFIG
    from repro.core import ising
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    cache_events = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda event, **kw: cache_events.update([event.rsplit("/", 1)[-1]]))

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"device: platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}")
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is on platform "
              f"{device['platform']!r}", file=sys.stderr)
        return 1
    if len(devs) < args.devices:
        print(f"chip_smoke: --devices {args.devices} needs {args.devices} chips, "
              f"found {len(devs)}", file=sys.stderr)
        return 1

    m = ising.random_layered_model(n=CONFIG.spins_per_layer, L=CONFIG.num_layers,
                                   seed=args.seed)
    log(f"model: {m.n} spins x {m.L} layers = {m.num_spins} spins per replica, "
        f"{SLOTS} slots, seed {args.seed}; compile cache {cache_dir}")
    t0 = time.perf_counter()
    if args.devices == 1:
        single_chip_phases(m, args.seed)
    else:
        mesh_phase(m, args.devices)
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s; compile cache: "
        f"{cache_events['cache_hits']} hits, {cache_events['cache_misses']} "
        "entries written")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
