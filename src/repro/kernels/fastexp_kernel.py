"""Pallas TPU kernel for the paper's bit-trick exponential (§2.4).

Elementwise VPU kernel: integer multiply-round-bitcast, no transcendental
unit, no table.  This is the TPU-native form of the paper's SSE exp — all
8x128 VPU lanes evaluate one exp per cycle-ish, versus the multi-op
polynomial XLA emits for ``jnp.exp``.

Tiling: inputs are processed in (BLOCK_ROWS, 128) VMEM blocks — the minor
dimension matches the 128-wide TPU lane register exactly, rows are a
multiple of the 8-sublane f32 tile.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core import fastexp as fx

BLOCK_ROWS = 256
LANES = 128


def _fast_body(x_ref, o_ref):
    x = x_ref[...]
    i = lax.convert_element_type(
        x * jnp.float32((1 << 23) * fx.LOG2_E), jnp.int32
    ) + jnp.int32(127 << 23)
    o_ref[...] = lax.bitcast_convert_type(i, jnp.float32) * jnp.float32(
        fx.TWO_LN2_SQ
    )


def _accurate_body(x_ref, o_ref):
    x = x_ref[...]
    xc = jnp.clip(
        x, jnp.float32(fx.ACCURATE_LO), jnp.float32(fx.ACCURATE_HI - 1e-3)
    )
    i4 = lax.convert_element_type(
        xc * jnp.float32((1 << 25) * fx.LOG2_E), jnp.int32
    ) + jnp.int32(127 << 23)
    f = lax.bitcast_convert_type(i4, jnp.float32) * jnp.float32(fx.TWO_LN2_SQ)
    r = lax.rsqrt(lax.rsqrt(f))
    r = jnp.where(x < jnp.float32(fx.ACCURATE_LO), jnp.float32(0.0), r)
    o_ref[...] = jnp.where(x > 0, jnp.maximum(r, jnp.float32(1.0)), r)


@functools.partial(jax.jit, static_argnames=("flavor", "interpret", "block_rows"))
def fastexp_2d(
    x: jax.Array,
    flavor: str = "fast",
    *,
    interpret: bool,
    block_rows: int = BLOCK_ROWS,
) -> jax.Array:
    """Apply the approximation to a (rows, 128*k) float32 array via Pallas."""
    assert x.ndim == 2 and x.shape[1] % LANES == 0, x.shape
    rows, cols = x.shape
    body = _fast_body if flavor == "fast" else _accurate_body
    br = min(block_rows, rows)
    grid = (pl.cdiv(rows, br), cols // LANES)
    return pl.pallas_call(
        body,
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        grid=grid,
        in_specs=[pl.BlockSpec((br, LANES), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((br, LANES), lambda i, j: (i, j)),
        interpret=interpret,
    )(x.astype(jnp.float32))
