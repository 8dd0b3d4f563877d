"""Hypothesis property tests for the jittable assignment solver.

Pinned triangle on random R <= C <= 8 cost matrices (ties and
_PSI-masked infeasible cells included):

    hungarian_min_jax == hungarian_min == brute-force enumeration

— *identical assignments* for the jax/numpy pair (same algorithm, same
first-minimum tie-breaks), equal total cost against brute force.

Kept separate from tests/test_ddsra_jax.py so a container without
hypothesis still runs the full control-plane parity suite.
"""
import itertools

import numpy as np
import pytest

pytest.importorskip("hypothesis")  # container may lack hypothesis
from hypothesis import given, settings, strategies as st

import jax

from repro.core.hungarian import (assign_channels, assign_channels_jax,
                                  hungarian_min, hungarian_min_jax)

_PSI = 1e18
_jit_hungarian = jax.jit(hungarian_min_jax)


def _brute_force_min(cost: np.ndarray) -> float:
    r, c = cost.shape
    return min(sum(cost[i, p[i]] for i in range(r))
               for p in itertools.permutations(range(c), r))


@settings(max_examples=50, deadline=None)
@given(st.integers(1, 8), st.integers(0, 8), st.integers(0, 2 ** 31 - 1),
       st.sampled_from(["float", "ties", "psi"]))
def test_hungarian_jax_triangle(r, extra, seed, kind):
    c = min(r + extra, 8)
    rng = np.random.default_rng(seed)
    cost = rng.uniform(0, 10, (r, c))
    if kind == "ties":
        cost = np.round(cost)                    # many equal-cost optima
    elif kind == "psi":
        cost[rng.uniform(size=cost.shape) < 0.3] = _PSI
    cols_np, total_np = hungarian_min(cost)
    with jax.enable_x64(True):
        cols_jx, total_jx = _jit_hungarian(cost)
    assert np.array_equal(cols_np, np.asarray(cols_jx))
    assert float(total_jx) == pytest.approx(total_np, abs=1e-9)
    assert total_np == pytest.approx(_brute_force_min(cost),
                                     rel=1e-12, abs=1e-9)


@settings(max_examples=30, deadline=None)
@given(st.integers(2, 7), st.integers(1, 5), st.integers(0, 2 ** 31 - 1),
       st.booleans())
def test_assign_channels_jax_property(m, j, seed, with_psi):
    """Exact incidence-matrix parity + constraints C2/C3, with and without
    _PSI-banned cells (including a fully-banned gateway row)."""
    j = min(j, m)
    rng = np.random.default_rng(seed)
    theta = rng.normal(size=(m, j))
    if with_psi:
        theta[rng.uniform(size=theta.shape) < 0.25] = _PSI
        theta[rng.integers(m), :] = _PSI
    eye_np = assign_channels(theta)
    with jax.enable_x64(True):
        eye_jx = np.asarray(assign_channels_jax(theta))
    assert np.array_equal(eye_np, eye_jx)
    assert (eye_jx.sum(axis=0) == 1).all()
    assert (eye_jx.sum(axis=1) <= 1).all()
