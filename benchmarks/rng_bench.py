"""Paper §3: interlaced MT19937 throughput (the 'nearly 4x' claim).

Compares randoms/second from a single scalar-state generator (V=1) against
V-way interlaced generation (V = 4 paper SSE, 128 TPU lanes), plus the
Pallas kernel in interpret mode (correctness rung).  On CPU-JAX the
vector width is exploited by XLA's vectorizer; the metric is randoms/sec.
"""

from __future__ import annotations

import numpy as np

from benchmarks.common import time_fn
from repro.core import mt19937 as mt


def run():
    rows = []
    blocks = 32
    for V in (1, 4, 32, 128):
        seeds = np.arange(max(V, 1), dtype=np.uint32) + 1
        state = mt.mt_init(seeds if V > 1 else seeds[0])

        def gen(state=state):
            s, u = mt.mt_uniform_blocks(state, blocks)
            return u

        dt, out = time_fn(gen, iters=3, warmup=1)
        n = out.size
        rows.append((f"mt19937_V{V}", dt / n * 1e6, f"{n/dt/1e6:.2f}Mrand/s"))

    # The Pallas kernel (compiled on a TPU, interpret mode elsewhere):
    # fused twist+temper+float-convert emitting uniforms directly.
    from repro.kernels import ops

    state = mt.mt_init(np.arange(128, dtype=np.uint32) + 1)
    dt, out = time_fn(
        lambda: ops.mt_uniform_blocks(state, blocks), iters=3, warmup=1
    )
    n = out[1].size
    mode = "interpret mode" if ops._auto_interpret(None) else "compiled"
    rows.append(
        (f"mt19937_kernel_V128", dt / n * 1e6, f"{n/dt/1e6:.2f}Mrand/s ({mode})")
    )
    return rows


if __name__ == "__main__":
    for r in run():
        print(",".join(str(x) for x in r))
