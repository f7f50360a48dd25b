"""Pallas TPU kernels for the fully-vectorized Metropolis sweep (paper §3.1/3.2).

TPU adaptation of the paper's A.4/B.2 rungs: the model's L layers are
interlaced across the 128 TPU lanes (reorder.py), so one VPU op advances 128
spins — the CPU version's 4-wide SSE and the GPU version's 32-thread
coalesced warp both map to the lane dimension here.  The grid runs over
TILES of ``bt`` replicas; one tile's whole state lives in VMEM:

    spins/h_space/h_tau: 3 x rows x 128 x 4 B   (rows = L/128 * n)
    MT19937 state:       624 x 128 x 4 B = 312 KiB
    staged uniforms:     rows x 128 x 4 B       (scratch)

per replica.  With the blocks double-buffered, the paper's production
shape (256 layers x 96 spins, rows=192) takes about 2.5 MiB per replica;
`pick_replica_tile` sizes ``bt`` to a VMEM budget so every slot count
compiles.

Both sweep orders share one layout and one RNG stage:

* Row values are ``(bt, 128)``: ``ref[:, q, :]`` takes row ``q`` of every
  replica of the tile, so each VPU op advances the tile in lockstep.
* Each sweep first regenerates its uniforms in VMEM (`_draw_uniforms`):
  per replica, ``blocks = ceil(rows/624)`` in-place twists of the tile's
  (624, bt*128) interlaced MT19937 block (`mt19937.mt_twist_inplace`),
  temper, 24-bit floats, tail discarded — the host path's draw pattern
  (`mt.mt_uniforms_count`), so the two backends consume identical streams.
* Neighbour sites come from SMEM (the base graph's ``(n*SD,)`` table);
  couplings are one packed per-site row, read from SMEM as scalars when
  the tile shares one model, or as per-slot ``(bt, 1)`` columns of a VMEM
  ``(bt, n, K)`` block when each slot sweeps its own model (multi-tenant).
  With ``bt`` copies of one table the floats are identical either way.

The two orders:

* ``metropolis_multisweep_kernel`` — the sequential-order A.4 rung: rows
  are visited in order, each visit flips the row and adds its
  contribution to the neighbour rows' carried fields (lane rotation at the
  section-boundary rows), exactly `metropolis.sweep_lane`'s op order.
* ``make_colored_multisweep_kernel`` — the graph-colored "cb" rung: rows
  are visited class by class (`reorder.colored_classes`); each visit
  recomputes the row's fields from the current spins and flips it.  Rows
  of one class never interact, so flipping them one after another reads
  the same values as `metropolis.colored_flip_spins`' whole-class gather
  and the result is bit-identical to the jnp backend.  Fields are
  recomputed once per launch from the final spins (`metropolis.lane_h_eff`
  row by row).

``interpret`` has no default at any entry: `kernels.ops` is the one place
that chooses it (compiled on a TPU, the interpreter elsewhere).

``metropolis_sweep_kernel`` (single sweep, host-generated uniforms) is
DEPRECATED — it survives one release as a shim over the A.4 body at
``num_sweeps=1`` for the launch-structure benchmark and the historical
oracle tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import fastexp as fx
from repro.core import mt19937 as mt

LANES = 128
f32 = jnp.float32

#: VMEM a replica tile may fill (v5e has 128 MiB per core).
TILE_VMEM_BUDGET = 48 << 20
#: Mosaic's default scoped-VMEM limit on v5e; a tile that needs more (with
#: headroom for compiler temporaries) raises the limit to what it needs.
DEFAULT_SCOPED_VMEM = 16 << 20
VMEM_HEADROOM = 8 << 20


def _vmem_bytes(sublanes: int, lanes: int) -> int:
    """f32 bytes of a (sublanes, lanes) block padded to (8, 128) tiles."""
    return -(-sublanes // 8) * 8 * (-(-lanes // LANES)) * LANES * 4


def replica_vmem_bytes(rows: int, n: int, k: int, *, fields_in: bool, multi: bool) -> int:
    """VMEM one replica adds to a tile: its double-buffered input and
    output blocks plus its staged uniforms.  ``fields_in`` (A.4) ships the
    carried fields in; ``multi`` adds the per-slot (n, k) coupling block."""
    field = _vmem_bytes(rows, LANES)
    gen = _vmem_bytes(mt.N, LANES)
    ins = field * (3 if fields_in else 1) + gen + _vmem_bytes(1, LANES)
    if multi:
        ins += _vmem_bytes(n, k)
    outs = 3 * field + gen
    return 2 * (ins + outs) + field


def pick_replica_tile(batch: int, per_replica: int) -> int:
    """Largest divisor of ``batch`` whose tile fits `TILE_VMEM_BUDGET`."""
    fits = [
        d for d in range(1, batch + 1)
        if batch % d == 0 and d * per_replica <= TILE_VMEM_BUDGET
    ]
    return max(fits, default=1)


def _tile(batch: int, replica_tile: int | None, per_replica: int) -> int:
    if replica_tile is None:
        return pick_replica_tile(batch, per_replica)
    if batch % replica_tile != 0:
        raise ValueError(f"replica_tile {replica_tile} must divide batch {batch}")
    return replica_tile


def _compiler_params(bt: int, per_replica: int):
    need = bt * per_replica + VMEM_HEADROOM
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",),
        vmem_limit_bytes=need if need > DEFAULT_SCOPED_VMEM else None,
    )


def _coupling_reader(cpl_ref, k: int, multi: bool):
    """``coef(i, c)``: coupling column ``c`` of site ``i`` — a per-slot
    ``(bt, 1)`` column (multi-tenant VMEM block) or one SMEM scalar."""
    if multi:
        return lambda i, c: cpl_ref[:, i, pl.ds(c, 1)]
    return lambda i, c: cpl_ref[i * k + c]


def _draw_uniforms(rng_ref, u_ref, bt: int, rows: int, blocks: int) -> None:
    """Stage one sweep's uniforms: ``u_ref[b, q]`` is row ``q`` of replica
    ``b``, drawn from its 128 generator columns of ``rng_ref`` as
    ``blocks = ceil(rows/624)`` fresh blocks with the tail discarded."""

    def replica(b, carry):
        cols = pl.ds(pl.multiple_of(b * LANES, LANES), LANES)
        for k in range(blocks):  # static, blocks is tiny
            mt.mt_twist_inplace(rng_ref, cols)
            m = min(mt.N, rows - k * mt.N)
            u_ref[b, k * mt.N : k * mt.N + m, :] = mt.uniforms_from_u32(
                mt.mt_temper(rng_ref[0:m, cols])
            )
        return carry

    lax.fori_loop(0, bt, replica, 0)


def _a4_body(
    n: int,
    sd: int,
    rows: int,
    bt: int,
    num_sweeps: int,
    exp_flavor: str,
    multi: bool,
    host_uniforms: bool,
):
    """Sequential-order sweep body over a tile of ``bt`` replicas.

    ``host_uniforms=True`` is the DEPRECATED single-sweep flavour: the
    uniforms arrive as an input block instead of being drawn in-kernel.
    """
    exp_fn = fx.EXP_FNS[exp_flavor]
    k = sd + 1  # per-site couplings: doubled J_0..J_{sd-1}, doubled tau J
    blocks = -(-rows // mt.N)

    def body(nbr_ref, cpl_ref, beta_ref, spins_ref, hs_ref, ht_ref, src_ref, *outs):
        if host_uniforms:
            o_spins, o_hs, o_ht = outs
            u_ref = src_ref
        else:
            o_spins, o_hs, o_ht, o_rng, u_ref = outs
            o_rng[...] = src_ref[...]
        o_spins[...] = spins_ref[...]
        o_hs[...] = hs_ref[...]
        o_ht[...] = ht_ref[...]
        beta = beta_ref[:, 0, :]  # (bt, 128)
        coef = _coupling_reader(cpl_ref, k, multi)

        def rmw(ref, row, contrib):
            ref[:, row, :] = ref[:, row, :] + contrib

        def row_step(q, wrap):
            i = lax.rem(q, n)
            s = o_spins[:, q, :]
            x = (f32(-2.0) * beta) * s * (o_hs[:, q, :] + o_ht[:, q, :])
            mask = (u_ref[:, q, :] < exp_fn(x)).astype(f32)  # Figure 10 select
            smul = s * mask
            o_spins[:, q, :] = s * (f32(1.0) - f32(2.0) * mask)
            base = q - i
            for d in range(sd):  # static unroll over the sparse degree
                rmw(o_hs, base + nbr_ref[i * sd + d], -smul * coef(i, d))
            tc = -smul * coef(i, sd)
            if wrap == -1:  # first layer block: the down-link wraps a lane
                rmw(o_ht, rows - n + i, pltpu.roll(tc, LANES - 1, 1))
                rmw(o_ht, q + n, tc)
            elif wrap == +1:  # last layer block: the up-link wraps a lane
                rmw(o_ht, q - n, tc)
                rmw(o_ht, i, pltpu.roll(tc, 1, 1))
            else:
                rmw(o_ht, q - n, tc)
                rmw(o_ht, q + n, tc)

        def rows_loop(lo, hi, wrap):
            lax.fori_loop(lo, hi, lambda q, c: (row_step(q, wrap), c)[1], 0)

        def sweep(_k, carry):
            if not host_uniforms:
                _draw_uniforms(o_rng, u_ref, bt, rows, blocks)
            rows_loop(0, n, -1)
            rows_loop(n, rows - n, 0)
            rows_loop(rows - n, rows, +1)
            return carry

        lax.fori_loop(0, num_sweeps, sweep, 0)

    return body


def _beta_lanes(beta: jax.Array, batch: int) -> jax.Array:
    """(B,) betas as a (B, 1, 128) lane-dense block: a (bt, 1, 128) tile
    satisfies the TPU tiling rule for every replica tile size."""
    return jnp.broadcast_to(
        jnp.reshape(beta, (batch, 1, 1)).astype(f32), (batch, 1, LANES)
    )


def _a4_call(
    spins, h_space, h_tau, src, base_nbr, cpl, beta,
    *, n, num_sweeps, exp_flavor, interpret, replica_tile, multi, host_uniforms,
):
    """The one launch configuration of every A.4 entry.  ``src`` is the
    (624, B*128) generator state, or (host_uniforms) the (B, rows, 128)
    uniforms; ``cpl`` is the packed coupling table (`_coupling_reader`)."""
    B, rows, lanes = spins.shape
    assert lanes == LANES, spins.shape
    sd = base_nbr.shape[-1]
    k = sd + 1
    per = replica_vmem_bytes(rows, n, k, fields_in=True, multi=multi)
    bt = _tile(B, replica_tile, per)
    body = _a4_body(n, sd, rows, bt, num_sweeps, exp_flavor, multi, host_uniforms)
    tile = pl.BlockSpec((bt, rows, LANES), lambda g: (g, 0, 0))
    gen = pl.BlockSpec((mt.N, bt * LANES), lambda g: (0, g))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    field = jax.ShapeDtypeStruct((B, rows, LANES), f32)
    if host_uniforms:
        src_spec, out_shape, out_specs, scratch = tile, (field,) * 3, (tile,) * 3, []
    else:
        assert src.shape == (mt.N, B * LANES), (src.shape, B)
        src_spec = gen
        out_shape = (field,) * 3 + (jax.ShapeDtypeStruct(src.shape, jnp.uint32),)
        out_specs = (tile,) * 3 + (gen,)
        scratch = [pltpu.VMEM((bt, rows, LANES), f32)]
    return pl.pallas_call(
        body,
        out_shape=out_shape,
        grid=(B // bt,),
        in_specs=[
            smem,
            pl.BlockSpec((bt, n, k), lambda g: (g, 0, 0)) if multi else smem,
            pl.BlockSpec((bt, 1, LANES), lambda g: (g, 0, 0)),
            tile,
            tile,
            tile,
            src_spec,
        ],
        out_specs=out_specs,
        scratch_shapes=scratch,
        compiler_params=_compiler_params(bt, per),
        interpret=interpret,
    )(
        jnp.reshape(base_nbr, (-1,)).astype(jnp.int32), cpl,
        _beta_lanes(beta, B), spins, h_space, h_tau, src,
    )


def _shared_a4_couplings(base_J2, tau_J2) -> jax.Array:
    """One model's doubled couplings packed per site, flat for SMEM."""
    n = base_J2.shape[0]
    return jnp.concatenate(
        [jnp.asarray(base_J2, f32), jnp.reshape(tau_J2, (n, 1)).astype(f32)], axis=1
    ).reshape(-1)


@functools.partial(jax.jit, static_argnames=("n", "exp_flavor", "interpret"))
def metropolis_sweep_kernel(
    spins: jax.Array,  # (B, rows, 128) f32 in {-1,+1}
    h_space: jax.Array,  # (B, rows, 128)
    h_tau: jax.Array,  # (B, rows, 128)
    u: jax.Array,  # (B, rows, 128) uniforms
    base_nbr: jax.Array,  # (n, SD) int32
    base_J2: jax.Array,  # (n, SD) f32
    tau_J2: jax.Array,  # (n,) or (n, 1) f32
    beta: jax.Array,  # (B,) or (B, 1) f32
    n: int,
    exp_flavor: str = "fast",
    *,
    interpret: bool,
):
    """DEPRECATED single-sweep kernel (host-generated uniforms, one launch
    per sweep): the A.4 body at ``num_sweeps=1``.  New code should use
    `metropolis_multisweep_kernel` (in-kernel RNG); this survives one
    release as the seed-architecture baseline that
    `benchmarks.kernel_bench.launch_structure_compare` measures against and
    as the entry the historical oracle tests exercise.
    """
    return _a4_call(
        spins, h_space, h_tau, u, base_nbr,
        _shared_a4_couplings(base_J2, tau_J2), beta,
        n=n, num_sweeps=1, exp_flavor=exp_flavor, interpret=interpret,
        replica_tile=None, multi=False, host_uniforms=True,
    )


@functools.partial(
    jax.jit,
    static_argnames=("n", "num_sweeps", "exp_flavor", "interpret", "replica_tile"),
)
def metropolis_multisweep_kernel(
    spins: jax.Array,  # (B, rows, 128) f32 in {-1,+1}
    h_space: jax.Array,  # (B, rows, 128)
    h_tau: jax.Array,  # (B, rows, 128)
    rng: jax.Array,  # (624, B*128) uint32 interlaced MT19937 state
    base_nbr: jax.Array,  # (n, SD) int32
    base_J2: jax.Array,  # (n, SD) f32
    tau_J2: jax.Array,  # (n,) or (n, 1) f32
    beta: jax.Array,  # (B,) or (B, 1) f32
    n: int,
    num_sweeps: int,
    exp_flavor: str = "fast",
    *,
    interpret: bool,
    replica_tile: int | None = None,
):
    """``num_sweeps`` fused sweeps for each of B replicas, RNG in-kernel.

    Returns ``(spins, h_space, h_tau, rng)`` — one `pallas_call`, no
    host-side uniform buffers, no per-sweep launches.  Replicas advance in
    lockstep inside the body (batched vector ops), and the grid runs over
    replica TILES of ``replica_tile`` replicas (default: the largest
    divisor of B that fits the VMEM budget); tiles are independent, so
    every tiling is bit-equal to every other.
    """
    return _a4_call(
        spins, h_space, h_tau, rng, base_nbr,
        _shared_a4_couplings(base_J2, tau_J2), beta,
        n=n, num_sweeps=num_sweeps, exp_flavor=exp_flavor, interpret=interpret,
        replica_tile=replica_tile, multi=False, host_uniforms=False,
    )


@functools.partial(
    jax.jit,
    static_argnames=("n", "num_sweeps", "exp_flavor", "interpret", "replica_tile"),
)
def metropolis_multisweep_multi_kernel(
    spins: jax.Array,  # (B, rows, 128) f32 in {-1,+1}
    h_space: jax.Array,  # (B, rows, 128)
    h_tau: jax.Array,  # (B, rows, 128)
    rng: jax.Array,  # (624, B*128) uint32 interlaced MT19937 state
    base_nbr: jax.Array,  # (n, SD) int32 — SHARED topology
    base_J2_b: jax.Array,  # (B, n, SD) f32 — PER-SLOT couplings
    tau_J2_b: jax.Array,  # (B, n) or (B, n, 1) f32 — PER-SLOT tau couplings
    beta: jax.Array,  # (B,) or (B, 1) f32
    n: int,
    num_sweeps: int,
    exp_flavor: str = "fast",
    *,
    interpret: bool,
    replica_tile: int | None = None,
):
    """Multi-tenant flavour of `metropolis_multisweep_kernel`: the coupling
    tables gain a leading replica dim and ride the replica grid as a
    batched VMEM input, so one fused launch advances B slots each sweeping
    its OWN model (same lattice topology — the neighbour table stays
    shared).  With B copies of one model's tables this is bit-identical to
    the single-model kernel (the per-replica float ops are the same).
    """
    B = spins.shape[0]
    cpl = jnp.concatenate(
        [base_J2_b.astype(f32), jnp.reshape(tau_J2_b, (B, n, 1)).astype(f32)], axis=2
    )
    return _a4_call(
        spins, h_space, h_tau, rng, base_nbr, cpl, beta,
        n=n, num_sweeps=num_sweeps, exp_flavor=exp_flavor, interpret=interpret,
        replica_tile=replica_tile, multi=True, host_uniforms=False,
    )


# -----------------------------------------------------------------------------
# Graph-colored "cb" rung: rows visited class by class, fields recomputed.
# -----------------------------------------------------------------------------


def _cb_body(n: int, sd: int, rows: int, bt: int, num_sweeps: int, exp_flavor: str, multi: bool):
    """Fused colored-sweep body over a tile of ``bt`` replicas.

    Per sweep: stage the uniforms, then visit the rows in class order
    (``order_ref``), each visit recomputing the row's fields from the
    current spins and flipping it — the row-by-row form of
    `metropolis.colored_flip_spins`, with each float op in the same order.
    The carried fields are a pure function of the final spins and are
    recomputed once per launch (`metropolis.lane_h_eff`, row by row).
    """
    exp_fn = fx.EXP_FNS[exp_flavor]
    k = sd + 2  # per-site couplings: h, J_0..J_{sd-1}, tau J (undoubled)
    blocks = -(-rows // mt.N)

    def body(nbr_ref, order_ref, cpl_ref, beta_ref, spins_ref, rng_ref,
             o_spins, o_hs, o_ht, o_rng, u_ref):
        o_spins[...] = spins_ref[...]
        o_rng[...] = rng_ref[...]
        beta = beta_ref[:, 0, :]  # (bt, 128)
        coef = _coupling_reader(cpl_ref, k, multi)

        def fields(r):
            i = lax.rem(r, n)
            base = r - i
            hs = jnp.broadcast_to(coef(i, 0), (bt, LANES))
            for d in range(sd):
                hs = hs + coef(i, 1 + d) * o_spins[:, base + nbr_ref[i * sd + d], :]
            first, last = r < n, r >= rows - n  # section-boundary layer blocks
            down = o_spins[:, jnp.where(first, rows - n + i, r - n), :]
            down = jnp.where(first, pltpu.roll(down, 1, 1), down)
            up = o_spins[:, jnp.where(last, i, r + n), :]
            up = jnp.where(last, pltpu.roll(up, LANES - 1, 1), up)
            return hs, coef(i, sd + 1) * (down + up)

        def flip_row(j, carry):
            r = order_ref[j]
            s = o_spins[:, r, :]
            hs, ht = fields(r)
            x = (f32(-2.0) * beta) * s * (hs + ht)
            mask = (u_ref[:, r, :] < exp_fn(x)).astype(f32)
            o_spins[:, r, :] = s * (f32(1.0) - f32(2.0) * mask)
            return carry

        def sweep(_k, carry):
            _draw_uniforms(o_rng, u_ref, bt, rows, blocks)
            lax.fori_loop(0, rows, flip_row, 0)
            return carry

        lax.fori_loop(0, num_sweeps, sweep, 0)

        def refresh_row(r, carry):
            hs, ht = fields(r)
            o_hs[:, r, :] = hs
            o_ht[:, r, :] = ht
            return carry

        lax.fori_loop(0, rows, refresh_row, 0)

    return body


def _cb_call(
    spins, rng, beta, base_nbr, order, cpl,
    *, n, num_sweeps, exp_flavor, interpret, replica_tile, multi,
):
    B, rows, lanes = spins.shape
    assert lanes == LANES, spins.shape
    assert rng.shape == (mt.N, B * LANES), (rng.shape, B)
    sd = base_nbr.shape[-1]
    k = sd + 2
    per = replica_vmem_bytes(rows, n, k, fields_in=False, multi=multi)
    bt = _tile(B, replica_tile, per)
    body = _cb_body(n, sd, rows, bt, num_sweeps, exp_flavor, multi)
    tile = pl.BlockSpec((bt, rows, LANES), lambda g: (g, 0, 0))
    gen = pl.BlockSpec((mt.N, bt * LANES), lambda g: (0, g))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    field = jax.ShapeDtypeStruct((B, rows, LANES), f32)
    return pl.pallas_call(
        body,
        out_shape=(field, field, field, jax.ShapeDtypeStruct(rng.shape, jnp.uint32)),
        grid=(B // bt,),
        in_specs=[
            smem,
            smem,
            pl.BlockSpec((bt, n, k), lambda g: (g, 0, 0)) if multi else smem,
            pl.BlockSpec((bt, 1, LANES), lambda g: (g, 0, 0)),
            tile,
            gen,
        ],
        out_specs=(tile, tile, tile, gen),
        scratch_shapes=[pltpu.VMEM((bt, rows, LANES), f32)],
        compiler_params=_compiler_params(bt, per),
        interpret=interpret,
    )(
        jnp.reshape(base_nbr, (-1,)).astype(jnp.int32), order, cpl,
        _beta_lanes(beta, B), spins, rng,
    )


def _visit_order(classes) -> jax.Array:
    """Row visit order of one colored sweep: class by class, rows ascending."""
    return jnp.asarray(np.concatenate([np.asarray(c.rows) for c in classes]), jnp.int32)


def make_colored_multisweep_kernel(
    classes,  # tuple of reorder.ColorClass (host numpy)
    h,  # (n,) f32
    base_nbr,  # (n, SD) int32
    base_J,  # (n, SD) f32 NOT doubled
    tau_J,  # (n,) f32 NOT doubled
    n: int,
    exp_flavor: str = "fast",
    *,
    interpret: bool,
    replica_tile: int | None = None,
):
    """Build the fused colored-sweep entry for one model.

    The visit order and coupling tables are built once per model and
    shipped as SMEM kernel inputs (Pallas forbids captured array
    constants), so the returned callable is simply ``fn(spins, rng, beta,
    num_sweeps) -> (spins, h_space, h_tau, rng)`` with ``num_sweeps``
    static.  Unlike the sequential kernels there are no h_space/h_tau
    *inputs*: the colored sweep recomputes fields from spins (DESIGN.md
    §Coloring), so shipping them in would be dead HBM traffic.
    """
    order = _visit_order(classes)
    cpl = jnp.concatenate(
        [
            jnp.reshape(jnp.asarray(h, f32), (n, 1)),
            jnp.asarray(base_J, f32),
            jnp.reshape(jnp.asarray(tau_J, f32), (n, 1)),
        ],
        axis=1,
    ).reshape(-1)
    nbr = jnp.asarray(base_nbr, jnp.int32)

    @functools.partial(jax.jit, static_argnums=(3,))
    def fn(spins, rng, beta, num_sweeps):
        return _cb_call(
            spins, rng, beta, nbr, order, cpl,
            n=n, num_sweeps=num_sweeps, exp_flavor=exp_flavor,
            interpret=interpret, replica_tile=replica_tile, multi=False,
        )

    return fn


def make_colored_multisweep_multi_kernel(
    classes,  # tuple of reorder.ColorClass (host numpy; structure)
    base_nbr,  # (n, SD) int32 — SHARED topology
    n: int,
    exp_flavor: str = "fast",
    *,
    interpret: bool,
    replica_tile: int | None = None,
):
    """Build the multi-tenant fused colored-sweep entry for one TOPOLOGY.

    Returns ``fn(spins, rng, beta, h_b, base_J_b, tau_J_b, num_sweeps) ->
    (spins, h_space, h_tau, rng)`` with the per-slot coupling tables as
    runtime ``[B, ...]`` inputs — unlike `make_colored_multisweep_kernel`,
    which closes over one model's couplings, this callable serves any
    model mix sharing the classes' lattice.
    """
    order = _visit_order(classes)
    nbr = jnp.asarray(base_nbr, jnp.int32)

    @functools.partial(jax.jit, static_argnums=(6,))
    def fn(spins, rng, beta, h_b, base_J_b, tau_J_b, num_sweeps):
        B = spins.shape[0]
        assert h_b.shape[0] == B and base_J_b.shape[0] == B
        cpl = jnp.concatenate(
            [
                jnp.reshape(h_b, (B, n, 1)).astype(f32),
                base_J_b.astype(f32),
                jnp.reshape(tau_J_b, (B, n, 1)).astype(f32),
            ],
            axis=2,
        )
        return _cb_call(
            spins, rng, beta, nbr, order, cpl,
            n=n, num_sweeps=num_sweeps, exp_flavor=exp_flavor,
            interpret=interpret, replica_tile=replica_tile, multi=True,
        )

    return fn
