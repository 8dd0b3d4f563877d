"""Jitted DDSRA control plane vs the numpy oracle.

Parity contract (pinned here, required by the control-plane refactor):
identical channel assignments and selected-gateway sets, Lambda and tau
within atol 1e-6 (x64), across random networks/rounds and through an
end-to-end Simulation run; the jittable Hungarian is the numpy algorithm
step for step (identical assignments, not merely equal cost); the round
function compiles exactly once per network shape.
"""
import itertools

import numpy as np
import pytest

import jax

from repro.core import costmodel as cm
from repro.core import ddsra_jax
from repro.core.ddsra import Workload, ddsra_round
from repro.core.ddsra_jax import DDSRAPlan
from repro.core.hungarian import (assign_channels, assign_channels_jax,
                                  hungarian_min, hungarian_min_jax)
from repro.core.network import Network, NetworkConfig
from repro.core.participation import participation_rates


def _mlp_workload(n_devices: int, seed: int) -> Workload:
    from repro.models.vgg import mlp_layer_costs
    layers = mlp_layer_costs((3072, 512, 512, 10))
    o, g = cm.flops_vector(layers), cm.mem_vector(layers, batch=50)
    rng = np.random.default_rng(seed)
    d_tilde = np.maximum(
        (rng.uniform(0, 2000, n_devices) * 0.05).astype(int), 4)
    return Workload(o, g, cm.model_size_bytes(layers), 5,
                    d_tilde.astype(float))


# three shapes: the paper default, M == J, and a ragged shop-floor layout
# (26 devices over 8 gateways -> unequal per-gateway device counts)
_CONFIGS = [
    NetworkConfig(),
    NetworkConfig(n_gateways=5, n_channels=5, n_devices=15),
    NetworkConfig(n_gateways=8, n_channels=4, n_devices=26),
]


def test_round_parity_random_networks():
    """>= 50 random (network, round) pairs: identical assignment/selected,
    Lambda & tau atol 1e-6, identical per-device cuts on selected pairs."""
    compared = 0
    for ci, cfg in enumerate(_CONFIGS):
        net = Network(cfg, np.random.default_rng(100 + ci))
        w = _mlp_workload(cfg.n_devices, seed=ci)
        gamma = participation_rates(
            np.random.default_rng(ci).uniform(0.5, 2, cfg.n_gateways),
            cfg.n_channels)
        plan = DDSRAPlan.build(w, net)
        q = qj = np.zeros(cfg.n_gateways)
        for t in range(18):
            st = net.draw()
            v = [0.01, 10.0, 1000.0][t % 3]
            dec = ddsra_round(w, net, st, q, gamma, v)
            decj = plan.round(st, qj, gamma, v)
            assert np.array_equal(dec.assignment, decj.assignment), (ci, t)
            assert np.array_equal(dec.selected, decj.selected), (ci, t)
            finite = np.isfinite(dec.lam)
            assert np.array_equal(finite, np.isfinite(decj.lam)), (ci, t)
            np.testing.assert_allclose(decj.lam[finite], dec.lam[finite],
                                       atol=1e-6, rtol=1e-9)
            assert abs(dec.delay - decj.delay) <= 1e-6, (ci, t)
            np.testing.assert_allclose(decj.queues, dec.queues, atol=1e-9)
            for key, sol in dec.solutions.items():
                solj = decj.solutions.get(key)
                if solj is None:          # jitted dict keeps assigned pairs
                    assert dec.assignment[key] == 0
                    continue
                assert np.array_equal(sol.l_split, solj.l_split), (ci, t)
                np.testing.assert_allclose(solj.f_gw, sol.f_gw, rtol=1e-6)
                assert abs(sol.p_tx - solj.p_tx) <= 1e-6 * max(sol.p_tx, 1)
            q, qj = dec.queues, decj.queues
            compared += 1
    assert compared >= 50


def test_round_compiles_once_across_rounds(compile_count):
    """Round-to-round reuse: one trace per network shape, zero after."""
    cfg = _CONFIGS[0]
    net = Network(cfg, np.random.default_rng(0))
    w = _mlp_workload(cfg.n_devices, seed=0)
    gamma = participation_rates(np.ones(cfg.n_gateways), cfg.n_channels)
    plan = DDSRAPlan.build(w, net)
    q = np.zeros(cfg.n_gateways)
    plan.round(net.draw(), q, gamma, 10.0)            # warm (or cached)
    with compile_count(ddsra_jax._round_jit) as c:
        for _ in range(5):
            q = plan.round(net.draw(), q, gamma, 10.0).queues
    assert c.count == 0


def test_scheduler_runs_in_x64_regardless_of_global_flag():
    """Precision contract: the control plane is x64 even when the data
    plane (and the global jax flag) stay f32."""
    cfg = _CONFIGS[0]
    net = Network(cfg, np.random.default_rng(0))
    w = _mlp_workload(cfg.n_devices, seed=0)
    plan = DDSRAPlan.build(w, net)
    out = plan.round_arrays(net.draw(), np.zeros(cfg.n_gateways),
                            np.ones(cfg.n_gateways), 10.0)
    assert out.lam.dtype == np.float64
    assert out.queues.dtype == np.float64
    assert plan.statics.cumf.dtype == np.float64


def test_e2e_simulation_policy_parity():
    """A full Simulation under policy="ddsra_jax" reproduces the oracle's
    round telemetry (selected/trained/cuts exactly, delay to 1e-6)."""
    from repro.fl import Scenario, Simulation
    sim = Simulation(Scenario(model="mlp", rounds=4, eval_every=2, seed=0))
    sim.reset()
    oracle = list(sim.rounds("ddsra"))
    sim.reset()
    jitted = list(sim.rounds("ddsra_jax"))
    assert len(oracle) == len(jitted) == 4
    for a, b in zip(oracle, jitted):
        assert np.array_equal(a.selected, b.selected)
        assert a.trained == b.trained
        assert np.array_equal(a.l_n, b.l_n)
        assert abs(a.delay - b.delay) <= 1e-6
        np.testing.assert_allclose(b.queues, a.queues, atol=1e-9)
        np.testing.assert_allclose(b.losses, a.losses, atol=1e-9)
        if a.accuracy is not None:
            assert b.accuracy == pytest.approx(a.accuracy, abs=1e-9)


def test_v_sweep_is_one_fused_program():
    """vmap-over-V device-resident sweep: right shapes, finite queues, and
    the Theorem-2 direction (small V honours participation targets)."""
    cfg = _CONFIGS[0]
    net = Network(cfg, np.random.default_rng(0))
    w = _mlp_workload(cfg.n_devices, seed=0)
    gamma = participation_rates(
        np.random.default_rng(2).uniform(0.5, 2, cfg.n_gateways),
        cfg.n_channels)
    plan = DDSRAPlan.build(w, net)
    taus, sel = plan.simulate_v_sweep(jax.random.PRNGKey(0), gamma,
                                      [0.01, 100.0], rounds=40)
    assert taus.shape == (2, 40)
    assert sel.shape == (2, 40, cfg.n_gateways)
    rates = sel[0].mean(axis=0)           # small V: constraint dominates
    assert (rates >= gamma - 0.2).all(), (rates, gamma)


# ---------------------------------------------------------------------------
# assignment solver: jitted Hungarian == numpy == brute force
# (the hypothesis property version lives in test_hungarian_jax_properties.py
#  so a container without hypothesis still runs everything above)
# ---------------------------------------------------------------------------

_PSI = 1e18
_jit_hungarian = jax.jit(hungarian_min_jax)


def _brute_force_min(cost: np.ndarray) -> float:
    r, c = cost.shape
    return min(sum(cost[i, p[i]] for i in range(r))
               for p in itertools.permutations(range(c), r))


def test_hungarian_jax_matches_numpy_and_bruteforce():
    """Identical assignment to the numpy oracle (same algorithm, same
    tie-breaks) and brute-force-optimal cost, on random R <= C <= 6
    matrices including ties and _PSI-masked infeasible cells."""
    rng = np.random.default_rng(0)
    with jax.enable_x64(True):
        for trial in range(60):
            r = int(rng.integers(1, 7))
            c = int(rng.integers(r, 7))
            cost = rng.uniform(0, 10, (r, c))
            if trial % 3 == 1:
                cost = np.round(cost)            # many equal-cost optima
            elif trial % 3 == 2:
                cost[rng.uniform(size=cost.shape) < 0.3] = _PSI
            cols_np, total_np = hungarian_min(cost)
            cols_jx, total_jx = _jit_hungarian(cost)
            assert np.array_equal(cols_np, np.asarray(cols_jx)), trial
            assert float(total_jx) == pytest.approx(total_np, abs=1e-9)
            assert total_np == pytest.approx(_brute_force_min(cost),
                                             rel=1e-12, abs=1e-9)


def test_assign_channels_jax_parity():
    """assign_channels_jax emits the oracle's exact 0/1 incidence matrix,
    including rounds where whole gateways are _PSI-banned."""
    rng = np.random.default_rng(1)
    with jax.enable_x64(True):
        for trial in range(40):
            m = int(rng.integers(2, 7))
            j = int(rng.integers(1, m + 1))
            theta = rng.normal(size=(m, j))
            if trial % 2:
                theta[rng.uniform(size=theta.shape) < 0.25] = _PSI
                theta[rng.integers(m), :] = _PSI   # fully-banned gateway
            eye_np = assign_channels(theta)
            eye_jx = np.asarray(assign_channels_jax(theta))
            assert np.array_equal(eye_np, eye_jx), trial
            assert (eye_jx.sum(axis=0) == 1).all()       # C3
            assert (eye_jx.sum(axis=1) <= 1).all()       # C2
