"""The benchmark's copy of the instance generators makes the same
instances as the service's own (`repro.core.ising`)."""

from __future__ import annotations

import numpy as np
import pytest

from harness import models


@pytest.mark.parametrize("seed", [0, 5, 2**31 + 9])
def test_generators_match_the_services(seed):
    from repro.core import ising

    ours = models.random_layered(96, 256, seed)
    theirs = ising.random_layered_model(96, 256, seed=seed)
    for k in ("h", "space_nbr", "space_J", "tau_J"):
        np.testing.assert_array_equal(ours[k], getattr(theirs, k))
    r_ours = models.reseeded(ours, seed + 3)
    r_theirs = ising.reseed_couplings(theirs, seed + 3)
    for k in ("h", "space_nbr", "space_J", "tau_J"):
        np.testing.assert_array_equal(r_ours[k], getattr(r_theirs, k))
