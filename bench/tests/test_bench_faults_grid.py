"""The comparison that decides ``correct`` in the policy-grid cell sees
each fault the cell can have, and the control: the harness's load generator and
comparison on the CPU at a tiny width, with the timed path broken
underneath."""
import jax
import numpy as np
import pytest

from bench import calibrate
from bench_fault_helpers import cell, correct_after, wrapped


@pytest.fixture
def grid():
    return cell("vgg11-paper.grid", rounds=6,
                check={"ddsra_answers": 12})


def test_grid_sound_run_is_correct(grid):
    assert correct_after(*grid)


def sweep_wrap(alter):
    def wrap(orig):
        def f(self, *a, **k):
            out = orig(self, *a, **k)
            out.taus, out.selected, out.queues = (
                np.array(out.taus), np.array(out.selected),
                np.array(out.queues))
            alter(out)
            return out
        return f
    return wrap


def _alter_tau(out):
    out.taus[..., -1] *= 1.01


def _half_lanes(out):
    s = out.taus.shape[1]
    for a in (out.taus, out.selected, out.queues):
        a[:, s // 2 + s % 2:] = 0


def _queues_unchanged(out):
    out.queues[...] = 0


@pytest.mark.parametrize("alter", [_alter_tau, _half_lanes,
                                   _queues_unchanged])
def test_grid_fault_fails(grid, alter):
    with wrapped("sweep", sweep_wrap(alter)):
        assert not correct_after(*grid)


def test_grid_control_fails(grid):
    jax.clear_caches()
    try:
        with calibrate.x64_off(jax):
            assert not correct_after(*grid)
    finally:
        jax.clear_caches()
