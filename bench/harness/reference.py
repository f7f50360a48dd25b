"""The plain reference of the served computation, written from the
algorithm's definition and importing nothing of the program.

The parts every job kind shares, for one or many replicas of a layered
QMC Ising model (arXiv:1004.0024 §2-§4, the graph-colored "cb" sweep
order); each kind's replay (``bench/kinds/<kind>.py``) is built on them:

* Layout: layer ``l`` of site ``i`` lives at row ``(l mod L/V) * n + i``,
  lane ``l div L/V`` of a (rows, V) tile.
* Sweep order: rows are colored so that no two rows of a class are
  coupled (first-fit coloring of the base graph, plus 0/1 over the layer
  blocks, 2 for the last block of an odd count); classes are visited in
  color order, and each class flips all its rows at once from fields
  recomputed from the current spins.
* Random numbers: every replica owns V interlaced MT19937 generators
  (Matsumoto-Nishimura), seeded ``lane * 2654435761 + seed``; each sweep
  draws ``ceil(rows/624)`` whole blocks and uses the first ``rows``
  outputs, as 24-bit uniforms.
* Flip: accept when ``u < exp(-2 beta s h)``, with the exponential
  computed by the bit trick of the paper's §2.4 ("fast" flavour).
* Fields and energies: the carried effective fields of a configuration,
  and its energy in float64.

Every float operation is written in the order the algorithm states it,
so a sound program reproduces the reference bit for bit.  ``ftype``
selects the float type of the field, energy and acceptance arithmetic:
float32 is the reference; bfloat16 is the control, the nearest precision
below the configuration's.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

f32 = jnp.float32

MT_N = 624
MT_M = 397
SEED_MULT = 2654435761

# -----------------------------------------------------------------------------
# Layout and coloring (host numpy, built once per lattice).
# -----------------------------------------------------------------------------


def lane_perm(n: int, L: int, V: int) -> np.ndarray:
    """perm[row * V + lane] = flat spin id (layer-major, id = l * n + i)."""
    lpv = L // V
    p, i, v = np.meshgrid(np.arange(lpv), np.arange(n), np.arange(V), indexing="ij")
    return ((v * lpv + p) * n + i).reshape(-1)


def to_lane(flat: np.ndarray, n: int, L: int, V: int) -> np.ndarray:
    return np.asarray(flat)[..., lane_perm(n, L, V)].reshape(
        np.asarray(flat).shape[:-1] + ((L // V) * n, V))


def from_lane(lane: np.ndarray, n: int, L: int, V: int) -> np.ndarray:
    lane = np.asarray(lane)
    lead = lane.shape[:-2]
    out = np.empty(lead + (n * L,), lane.dtype)
    out[..., lane_perm(n, L, V)] = lane.reshape(lead + (-1,))
    return out


def _first_fit(adj: list) -> np.ndarray:
    colors = np.full(len(adj), -1, np.int64)
    for v in range(len(adj)):
        used = {colors[u] for u in adj[v] if colors[u] >= 0}
        c = 0
        while c in used:
            c += 1
        colors[v] = c
    return colors


def color_classes(space_nbr: np.ndarray, n: int, lpv: int) -> list:
    """Rows of each color class, ascending, in color order."""
    adj = [set() for _ in range(n)]
    for i in range(n):
        for j in map(int, space_nbr[i]):
            if j != i:
                adj[i].add(j)
                adj[j].add(i)
    base = _first_fit(adj)
    cyc = np.arange(lpv) % 2
    if lpv % 2:
        cyc[-1] = 2
    C = int(max(base.max(), cyc.max())) + 1
    colors = ((cyc[:, None] + base[None, :]) % C).reshape(-1)
    return [np.nonzero(colors == c)[0] for c in range(C)]


class Lattice:
    """Gather tables of one lattice (shared by every instance on it)."""

    def __init__(self, n: int, L: int, V: int, space_nbr: np.ndarray):
        self.n, self.L, self.V = n, L, V
        self.lpv = L // V
        self.rows = self.lpv * n
        self.nbr = np.asarray(space_nbr, np.int64)
        self.sd = self.nbr.shape[1]
        self.classes = []
        for rows_c in color_classes(self.nbr, n, self.lpv):
            p, i = rows_c // n, rows_c % n
            self.classes.append(dict(
                rows=rows_c,
                site=i,
                tgt=p[:, None] * n + self.nbr[i],
                down=np.where(p == 0, (self.lpv - 1) * n + i, rows_c - n),
                up=np.where(p == self.lpv - 1, i, rows_c + n),
                down_roll=(p == 0),
                up_roll=(p == self.lpv - 1),
            ))


# -----------------------------------------------------------------------------
# MT19937, interlaced over columns.
# -----------------------------------------------------------------------------


def mt_seed(seeds) -> np.ndarray:
    """(624, K) uint32 state of K generators seeded with ``seeds``."""
    seeds = np.asarray(seeds, np.uint64) & 0xFFFFFFFF
    out = np.empty((MT_N,) + seeds.shape, np.uint64)
    out[0] = seeds
    for i in range(1, MT_N):
        prev = out[i - 1]
        out[i] = (1812433253 * (prev ^ (prev >> 30)) + i) & 0xFFFFFFFF
    return out.astype(np.uint32)


def lane_seeds(count: int, seed: int) -> np.ndarray:
    return ((np.arange(count, dtype=np.uint64) * SEED_MULT + seed)
            & 0xFFFFFFFF).astype(np.uint32)


def _twist(mt):
    """One full regeneration of the 624-word state (per column)."""
    def step(cur, nxt, far):
        y = (cur & np.uint32(0x80000000)) | (nxt & np.uint32(0x7FFFFFFF))
        return far ^ (y >> np.uint32(1)) ^ ((y & np.uint32(1)) * np.uint32(0x9908B0DF))
    a = step(mt[0:227], mt[1:228], mt[397:624])
    b = step(mt[227:454], mt[228:455], a)
    c = step(mt[454:623], mt[455:624], b[0:169])
    d = step(mt[623:624], a[0:1], b[169:170])
    return jnp.concatenate([a, b, c, d], axis=0)


def _temper(y):
    y = y ^ (y >> np.uint32(11))
    y = y ^ ((y << np.uint32(7)) & np.uint32(0x9D2C5680))
    y = y ^ ((y << np.uint32(15)) & np.uint32(0xEFC60000))
    return y ^ (y >> np.uint32(18))


def uniforms(mt, count: int):
    """``count`` uniforms per column from ceil(count/624) fresh blocks."""
    outs = []
    for _ in range(-(-count // MT_N)):
        mt = _twist(mt)
        outs.append(_temper(mt))
    u = jnp.concatenate(outs, axis=0)[:count]
    return mt, (u >> np.uint32(8)).astype(jnp.int32).astype(f32) * f32(1.0 / (1 << 24))


# -----------------------------------------------------------------------------
# The sweep.
# -----------------------------------------------------------------------------


def fast_exp(x):
    """exp(x) by the paper's bit trick: round(x * 2^23 log2 e) + 127 * 2^23
    read as a float, times 2 ln^2 2."""
    x = x.astype(f32)
    i = jax.lax.convert_element_type(x * f32((1 << 23) * math.log2(math.e)), jnp.int32)
    i = i + np.int32(127 << 23)
    return jax.lax.bitcast_convert_type(i, f32) * f32(2.0 * math.log(2.0) ** 2)


def _sweep(lat: Lattice, ftype, spins, u, betas, h, J, tau):
    """One colored sweep of B replicas. spins, u: (B, rows, V); betas: (B,);
    h, tau: (B, n); J: (B, n, SD)."""
    mb = (f32(-2.0) * betas).astype(ftype)[:, None, None]
    for c in lat.classes:
        rows, site = c["rows"], c["site"]
        s = spins[:, rows].astype(ftype)
        hs = jnp.broadcast_to(h[:, site][:, :, None].astype(ftype), s.shape)
        for d in range(lat.sd):
            hs = hs + J[:, site, d][:, :, None].astype(ftype) * spins[:, c["tgt"][:, d]].astype(ftype)
        down = spins[:, c["down"]]
        down = jnp.where(c["down_roll"][None, :, None], jnp.roll(down, 1, axis=-1), down)
        up = spins[:, c["up"]]
        up = jnp.where(c["up_roll"][None, :, None], jnp.roll(up, -1, axis=-1), up)
        ht = tau[:, site][:, :, None].astype(ftype) * (down + up).astype(ftype)
        x = mb * s * (hs + ht)
        accept = (u[:, rows] < fast_exp(x)).astype(f32)
        spins = spins.at[:, rows].set(spins[:, rows] * (f32(1.0) - f32(2.0) * accept))
    return spins


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def run_sweeps(lat: Lattice, ftype, num: int, spins, mt, betas, h, J, tau):
    B, rows, V = spins.shape

    def body(_, carry):
        spins, mt = carry
        mt, u = uniforms(mt, rows)
        u = u.reshape(rows, B, V).transpose(1, 0, 2)
        return _sweep(lat, ftype, spins, u, betas, h, J, tau), mt

    return jax.lax.fori_loop(0, num, body, (spins, mt))


def fields(lat: Lattice, spins, h, J, tau, ftype=f32):
    """Effective fields (h_space, h_tau) of (B, rows, V) spins."""
    B = spins.shape[0]
    s = spins.reshape(B, lat.lpv, lat.n, lat.V).astype(ftype)
    hs = jnp.broadcast_to(h[:, None, :, None].astype(ftype), s.shape)
    for d in range(lat.sd):
        hs = hs + J[:, None, :, d, None].astype(ftype) * s[:, :, lat.nbr[:, d], :]
    down = jnp.concatenate([jnp.roll(s[:, -1:], 1, axis=-1), s[:, :-1]], axis=1)
    up = jnp.concatenate([s[:, 1:], jnp.roll(s[:, :1], -1, axis=-1)], axis=1)
    ht = tau[:, None, :, None].astype(ftype) * (down + up)
    shape = (B, lat.rows, lat.V)
    return hs.reshape(shape).astype(f32), ht.reshape(shape).astype(f32)


def energy_f64(model: dict, spins_flat: np.ndarray) -> np.ndarray:
    """Energy of flat (..., N) spins in float64: -sum h s - sum_edges J s s."""
    n, L = model["n"], model["L"]
    s = np.asarray(spins_flat, np.float64).reshape(-1, L, n)
    nbr, J = np.asarray(model["space_nbr"]), np.asarray(model["space_J"], np.float64)
    e = -(s * np.asarray(model["h"], np.float64)).sum(axis=(1, 2))
    for d in range(nbr.shape[1]):
        e -= 0.5 * (J[:, d] * s * s[:, :, nbr[:, d]]).sum(axis=(1, 2))
    e -= (np.asarray(model["tau_J"], np.float64) * s * np.roll(s, -1, axis=1)).sum(axis=(1, 2))
    return e


def init_spins_flat(N: int, seed: int) -> np.ndarray:
    """The seeded start configuration: +-1 from numpy's generator at ``seed + 7``."""
    rng = np.random.default_rng(seed + 7)
    return np.where(rng.random(N) < 0.5, -1.0, 1.0).astype(np.float32)


def tables(models: list):
    """(h, J, tau) of the models, stacked, in float32."""
    return (jnp.asarray(np.stack([m["h"] for m in models]), f32),
            jnp.asarray(np.stack([m["space_J"] for m in models]), f32),
            jnp.asarray(np.stack([m["tau_J"] for m in models]), f32))
