"""MT19937 in JAX: scalar-compatible and V-way interlaced (paper §3).

The paper's key RNG optimization interlaces 4 independent MT19937 generators
so one SSE op advances all four.  Here the state is ``(624, V)`` uint32 and a
single blocked "twist" advances all V generators with pure vector ops — on
TPU, V=128 fills the lane dimension exactly (the paper's coalescing analogy).

The in-place twist has a sequential dependency (``mt[i]`` reads
``mt[(i+397) % 624]`` which may already be updated), so the vectorized twist
is split into three statically-sliced chunks plus the final element — the
same blocking a hand-vectorized SSE implementation uses:

    new[0:227]   = T(old[0:227],   old[1:228],   old[397:624])
    new[227:454] = T(old[227:454], old[228:455], new[0:227])
    new[454:623] = T(old[454:623], old[455:624], new[227:396])
    new[623]     = T(old[623],     new[0],       new[396])

Lane ``k`` of the interlaced generator reproduces, bit-exactly, a scalar
MT19937 seeded with ``seeds[k]`` (tested against the C++ ``std::mt19937``
known-answer values).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

N = 624
M = 397
MATRIX_A = np.uint32(0x9908B0DF)
UPPER_MASK = np.uint32(0x80000000)
LOWER_MASK = np.uint32(0x7FFFFFFF)
INIT_MULT = np.uint32(1812433253)
DEFAULT_SEED = 5489

# Tempering constants.
TEMPER_B = np.uint32(0x9D2C5680)
TEMPER_C = np.uint32(0xEFC60000)


@jax.jit
def _mt_init_scan(seeds: jax.Array) -> jax.Array:
    """Knuth-style seeding as one lax.scan over the 624 rows (the recurrence
    is sequential in i but vector across lanes).  uint32 wraparound is the
    algorithm; XLA uint32 arithmetic wraps identically to the NumPy
    reference, so this is bit-exact (tests/test_mt19937.py KATs)."""

    def step(prev, i):
        nxt = INIT_MULT * (prev ^ (prev >> np.uint32(30))) + i
        return nxt, nxt

    _, rest = jax.lax.scan(step, seeds, jnp.arange(1, N, dtype=jnp.uint32))
    return jnp.concatenate([seeds[None], rest], axis=0)


def mt_init(seeds) -> jax.Array:
    """Initialise interlaced state from per-lane seeds.

    Jitted (one compile per lane count, then ~sub-ms per call): the serve
    scheduler re-seeds a generator block on every job admission, so
    seeding is on the serving fast path, not just at startup.

    Args:
      seeds: scalar or (V,) array-like of uint32 seeds.
    Returns:
      (624,) uint32 state if scalar seed, else (624, V).
    """
    seeds = np.asarray(seeds, dtype=np.uint32)
    scalar = seeds.ndim == 0
    if scalar:
        seeds = seeds[None]
    state = _mt_init_scan(jnp.asarray(seeds))
    return state[:, 0] if scalar else state


def _twist_chunk(u: jax.Array, v: jax.Array, m: jax.Array) -> jax.Array:
    """One vectorized twist step: u=mt[i], v=mt[i+1], m=mt[i+M mod N]."""
    y = (u & UPPER_MASK) | (v & LOWER_MASK)
    # (y & 1) ? MATRIX_A : 0 — branch-free, exactly the paper's Figure 10.
    mag = (y & np.uint32(1)) * MATRIX_A
    return m ^ (y >> np.uint32(1)) ^ mag


def mt_twist(state: jax.Array) -> jax.Array:
    """Advance the full 624-entry state block (works for (624,) or (624, V))."""
    s = state
    p1 = _twist_chunk(s[0:227], s[1:228], s[397:624])        # new[0:227]
    p2 = _twist_chunk(s[227:454], s[228:455], p1[0:227])     # new[227:454]
    p3 = _twist_chunk(s[454:623], s[455:624], p2[0:169])     # new[454:623]
    last = _twist_chunk(s[623:624], p1[0:1], p2[169:170])    # new[623]
    return jnp.concatenate([p1, p2, p3, last], axis=0)


def mt_twist_inplace(ref, cols=slice(None)) -> None:
    """`mt_twist` in place on a (624, V) Pallas ref, over columns ``cols``.

    The same three chunks plus the last element.  Each chunk reads the
    entries an earlier chunk already rewrote (``new[0:227]``, ...) back
    from the ref, which is exactly what the value formulation passes
    along, so the result is bit-identical to `mt_twist`.
    """
    T = _twist_chunk
    ref[0:227, cols] = T(ref[0:227, cols], ref[1:228, cols], ref[397:624, cols])
    ref[227:454, cols] = T(ref[227:454, cols], ref[228:455, cols], ref[0:227, cols])
    ref[454:623, cols] = T(ref[454:623, cols], ref[455:624, cols], ref[227:396, cols])
    ref[623:624, cols] = T(ref[623:624, cols], ref[0:1, cols], ref[396:397, cols])


def mt_temper(y: jax.Array) -> jax.Array:
    """MT19937 output tempering (pure elementwise vector ops)."""
    y = y ^ (y >> np.uint32(11))
    y = y ^ ((y << np.uint32(7)) & TEMPER_B)
    y = y ^ ((y << np.uint32(15)) & TEMPER_C)
    y = y ^ (y >> np.uint32(18))
    return y


@functools.partial(jax.jit)
def mt_next_block(state: jax.Array):
    """Advance state and emit 624 tempered outputs per lane.

    Returns ``(new_state, outputs)`` with shapes matching ``state``.
    """
    new_state = mt_twist(state)
    return new_state, mt_temper(new_state)


def uniforms_from_u32(u32: jax.Array) -> jax.Array:
    """Map uint32 randoms to float32 uniforms in [0, 1).

    Uses the 24 high bits (exactly representable in float32), the standard
    choice for Metropolis accept tests.  The shifted value is below 2^24,
    so it converts through int32 exactly; the TPU compiler has no
    uint32 -> float32 conversion.
    """
    top24 = (u32 >> np.uint32(8)).astype(jnp.int32)
    return top24.astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


def mt_uniform_blocks(state: jax.Array, num_blocks: int):
    """Generate ``num_blocks`` blocks of 624 uniforms per lane.

    The paper generates many random numbers at a time to amortize overheads
    (§2.3 "result caching"); this is the JAX analogue — one scan, one big
    buffer out.

    Returns ``(new_state, uniforms)`` where uniforms has shape
    ``(num_blocks * 624,) + state.shape[1:]``.
    """

    def step(s, _):
        s, out = mt_next_block(s)
        return s, out

    state, blocks = jax.lax.scan(step, state, None, length=num_blocks)
    u = uniforms_from_u32(blocks.reshape((-1,) + blocks.shape[2:]))
    return state, u


def mt_uniforms_count(state: jax.Array, count: int):
    """Exactly ``count`` uniforms per lane: ceil(count/624) fresh blocks,
    tail discarded.

    This is THE draw pattern every sweep/swap consumer uses (engine jnp
    backend, fused Pallas kernel, tempering swap phase): discarding the
    tail instead of carrying it over keeps each call's stream position a
    pure function of (state, count), which is what makes host-side and
    in-kernel generation bit-exact replayable.

    Returns ``(new_state, uniforms)`` with uniforms shape
    ``(count,) + state.shape[1:]``.
    """
    state, u = mt_uniform_blocks(state, -(-count // N))
    return state, u[:count]


# ----------------------------------------------------------------------------
# Pure-NumPy scalar reference (the textbook sequential algorithm) used as the
# oracle in tests; deliberately written in the unvectorized in-place style of
# the original Matsumoto-Nishimura code.
# ----------------------------------------------------------------------------


class ScalarMT19937Ref:
    """Sequential in-place MT19937, matching C++ std::mt19937 output."""

    def __init__(self, seed: int = DEFAULT_SEED):
        self.mt = np.empty(N, dtype=np.uint32)
        self.mt[0] = np.uint32(seed)
        with np.errstate(over="ignore"):  # uint32 wraparound is the algorithm
            for i in range(1, N):
                prev = self.mt[i - 1]
                self.mt[i] = INIT_MULT * (prev ^ (prev >> np.uint32(30))) + np.uint32(i)
        self.index = N

    def _twist_inplace(self):
        mt = self.mt
        for i in range(N):
            y = (mt[i] & UPPER_MASK) | (mt[(i + 1) % N] & LOWER_MASK)
            mag = MATRIX_A if (y & np.uint32(1)) else np.uint32(0)
            mt[i] = mt[(i + M) % N] ^ (y >> np.uint32(1)) ^ mag
        self.index = 0

    def next_u32(self) -> int:
        if self.index >= N:
            self._twist_inplace()
        y = self.mt[self.index]
        self.index += 1
        y ^= y >> np.uint32(11)
        y ^= (y << np.uint32(7)) & TEMPER_B
        y ^= (y << np.uint32(15)) & TEMPER_C
        y ^= y >> np.uint32(18)
        return int(y)
