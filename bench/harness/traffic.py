"""The one traffic generator: turns a mix's parameters (a JSON file under
``bench/traffic/``) and the seed into job specs.

A mix's ``kind`` names the module under ``bench/kinds/`` that makes its
specs (`spec.kind`).  A kind of single-slot jobs takes them from a
`Stream`, which draws each job's budget, user, instance, seed and, in an
open loop, arrival, and leaves the kind to shape the budget into the
job's own fields.  A spec is a plain dict; ``arrival`` is in seconds
from the start of the stream, open loops only.

Sizes and gaps are drawn so that every seed gets the same work in another
order: budgets run through every allowed value once per block, and open
arrivals use the same block of exponential gaps, shuffled.
"""

from __future__ import annotations

import numpy as np

SEED_SPACE = 2**31  # job seeds stay far below the 32-bit generator seeds


class Stream:
    """Endless seeded stream of single-slot jobs; ``shape(mix, budget)``
    gives a job's own fields (it draws nothing)."""

    def __init__(self, mix: dict, instances: int, seed: int, shape):
        self.mix = mix
        self.shape = shape
        self.rng = np.random.default_rng([seed, 2])
        step = mix["sweeps_per_step"]
        lo, hi = mix["budget_min"], mix["budget_max"]
        if lo % step or hi % step:
            raise ValueError("budgets must be whole numbers of steps")
        self.budgets = np.arange(lo, hi + 1, step)
        users = mix["users"]
        w = 1.0 / np.arange(1, users + 1) ** mix["zipf_s"]
        self.user_p = w / w.sum()
        if instances % users:
            raise ValueError("instances must split evenly among users")
        self.per_user = instances // users
        rate = mix.get("rate_per_s")
        if rate:
            q = (np.arange(mix["gap_block"]) + 0.5) / mix["gap_block"]
            gaps = -np.log1p(-q)  # exponential quantiles, scaled to the rate
            self.gaps = gaps / gaps.mean() / rate
        else:
            self.gaps = None
        self._budgets, self._gaps = [], []
        self.clock = 0.0

    def _next_budget(self) -> int:
        if not self._budgets:
            self._budgets = list(self.rng.permutation(self.budgets))
        return int(self._budgets.pop())

    def _next_gap(self) -> float:
        if not self._gaps:
            self._gaps = list(self.rng.permutation(self.gaps))
        return float(self._gaps.pop())

    def next(self) -> dict:
        spec = self.shape(self.mix, self._next_budget())
        user = int(self.rng.choice(len(self.user_p), p=self.user_p))
        inst = user * self.per_user + int(self.rng.integers(self.per_user))
        spec.update(seed=int(self.rng.integers(SEED_SPACE)), user=f"user{user:02d}",
                    instance=inst, arrival=None)
        if self.gaps is not None:
            self.clock += self._next_gap()
            spec["arrival"] = self.clock
        return spec

    def take(self, k: int) -> list:
        return [self.next() for _ in range(k)]
