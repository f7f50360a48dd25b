"""Pallas TPU kernels for V-way interlaced MT19937 (paper §3).

One kernel invocation advances a (624, 128) block of generator state — 128
interlaced generators, one per TPU lane — and emits 624 tempered outputs per
lane.  The twist is the 3-chunk blocked formulation (see core/mt19937.py);
everything is uint32 VPU bitwise math on whole (chunk, 128) tiles, the
direct analogue of the paper's 4-lane SSE interlacing.

Two output flavours:

* ``mt_next_block_kernel``   — raw tempered uint32 outputs (the historical
  contract, validated bit-exactly against ``ref.mt_next_block_ref``).
* ``mt_uniforms_kernel``     — fuses the 24-bit float conversion into the
  same kernel, emitting float32 uniforms in [0, 1) directly; the host never
  touches raw u32 words.

Both are standalone conveniences: the Metropolis *sweep* kernel
(metropolis_kernel.metropolis_multisweep_kernel) goes one step further and
runs this exact twist/temper/convert pipeline inside the sweep body, so the
production path never materialises uniforms in HBM at all.

The full state block (624*128*4 B = 312 KiB) plus outputs fit comfortably
in one core's ~16 MiB VMEM, so blocks are whole-array and the grid runs
over independent 128-lane generator groups.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import mt19937 as mt

LANES = 128


def _mt_body(state_ref, new_state_ref, out_ref):
    s = state_ref[...]
    new = mt.mt_twist(s)  # pure uint32 vector ops, statically sliced chunks
    new_state_ref[...] = new
    out_ref[...] = mt.mt_temper(new)


def _mt_uniform_body(state_ref, new_state_ref, u_ref):
    s = state_ref[...]
    new = mt.mt_twist(s)
    new_state_ref[...] = new
    u_ref[...] = mt.uniforms_from_u32(mt.mt_temper(new))


def _block_call(body, state, out_dtype, interpret):
    assert state.shape[0] == mt.N and state.shape[1] % LANES == 0, state.shape
    groups = state.shape[1] // LANES
    return pl.pallas_call(
        body,
        out_shape=(
            jax.ShapeDtypeStruct(state.shape, jnp.uint32),
            jax.ShapeDtypeStruct(state.shape, out_dtype),
        ),
        grid=(groups,),
        in_specs=[pl.BlockSpec((mt.N, LANES), lambda g: (0, g))],
        out_specs=(
            pl.BlockSpec((mt.N, LANES), lambda g: (0, g)),
            pl.BlockSpec((mt.N, LANES), lambda g: (0, g)),
        ),
        interpret=interpret,
    )(state)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mt_next_block_kernel(state: jax.Array, *, interpret: bool):
    """Advance interlaced state (624, V) with V a multiple of 128.

    Returns (new_state, tempered uint32 outputs), both (624, V).
    """
    return _block_call(_mt_body, state, jnp.uint32, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mt_uniforms_kernel(state: jax.Array, *, interpret: bool):
    """Advance interlaced state and emit float32 uniforms directly.

    The temper + 24-bit float conversion is fused into the kernel — one
    launch yields (new_state (624, V) uint32, uniforms (624, V) float32).
    """
    return _block_call(_mt_uniform_body, state, jnp.float32, interpret)


def mt_uniform_blocks_kernel(state: jax.Array, num_blocks: int, *, interpret: bool):
    """Bulk uniforms via the fused kernel: scan of kernel steps (paper §2.3)."""

    def step(s, _):
        s, u = mt_uniforms_kernel(s, interpret=interpret)
        return s, u

    state, blocks = jax.lax.scan(step, state, None, length=num_blocks)
    return state, blocks.reshape((-1,) + blocks.shape[2:])
