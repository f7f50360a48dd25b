"""Problem instances made from the seed, by the benchmark itself.

A copy of the service's own generators (a ring plus random chords with
Gaussian couplings, and fresh disorder on the same lattice), so that the
couplings both the program and the reference read are made here and not
by the program.  Instances are plain dicts of numpy arrays.
"""

from __future__ import annotations

import numpy as np


def random_layered(n: int, L: int, seed: int, *, target_degree: int = 5,
                   beta: float = 1.0, j_scale: float = 1.0, h_scale: float = 0.3,
                   tau_scale: float = 0.5) -> dict:
    """An L-layer model of an n-site base graph of in-layer degree 4-6."""
    rng = np.random.default_rng(seed)
    adj = {i: set() for i in range(n)}

    def add(a: int, b: int) -> None:
        if a == b or b in adj[a]:
            return
        if len(adj[a]) > target_degree or len(adj[b]) > target_degree:
            return
        adj[a].add(b)
        adj[b].add(a)

    for i in range(n):
        add(i, (i + 1) % n)
    for _ in range((target_degree - 2) * n // 2):
        a, b = rng.integers(0, n, size=2)
        add(int(a), int(b))
    sd = max(len(v) for v in adj.values())
    nbr = np.tile(np.arange(n, dtype=np.int32)[:, None], (1, sd))
    J = np.zeros((n, sd), np.float32)
    edge = {}
    for i in range(n):
        for j in sorted(adj[i]):
            key = (min(i, j), max(i, j))
            if key not in edge:
                edge[key] = float(rng.normal() * j_scale)
    for i in range(n):
        for d, j in enumerate(sorted(adj[i])):
            nbr[i, d] = j
            J[i, d] = edge[(min(i, j), max(i, j))]
    h = (rng.normal(size=n) * h_scale).astype(np.float32)
    tau = np.full((n,), tau_scale, np.float32) * (
        1.0 + 0.1 * rng.normal(size=n).astype(np.float32))
    return dict(n=n, L=L, h=h, space_nbr=nbr, space_J=J, tau_J=tau, beta=beta)


def reseeded(base: dict, seed: int, *, j_scale: float = 1.0, h_scale: float = 0.3,
             tau_scale: float = 0.5) -> dict:
    """A fresh disorder realization on the lattice of ``base``."""
    rng = np.random.default_rng(seed + 1009)
    nbr = base["space_nbr"]
    n, sd = nbr.shape
    J = np.zeros((n, sd), np.float32)
    edge = {}
    for i in range(n):
        for d in range(sd):
            j = int(nbr[i, d])
            if j == i:
                continue
            key = (min(i, j), max(i, j))
            if key not in edge:
                edge[key] = float(rng.normal() * j_scale)
            J[i, d] = edge[key]
    h = (rng.normal(size=n) * h_scale).astype(np.float32)
    tau = np.full((n,), tau_scale, np.float32) * (
        1.0 + 0.1 * rng.normal(size=n).astype(np.float32))
    return dict(base, h=h, space_J=J, tau_J=tau)


def instances(cfg: dict, seed: int) -> list:
    """The configuration's ``instances`` disorder realizations, drawn from
    ``seed`` on the deployment's one lattice (the base graph of
    ``lattice_seed``, which the configuration fixes)."""
    m = cfg["model"]
    gen = {k: m[k] for k in ("target_degree", "j_scale", "h_scale", "tau_scale")}
    base = random_layered(m["spins_per_layer"], m["num_layers"], m["lattice_seed"], **gen)
    scale = {k: m[k] for k in ("j_scale", "h_scale", "tau_scale")}
    return [reseeded(base, seed + k, **scale) for k in range(cfg["instances"])]
