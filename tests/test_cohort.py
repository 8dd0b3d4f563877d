"""Cohort engine correctness: numerical parity with the seed sequential
path, single-compile behaviour across varying device subsets, and gradient
parity of the fused_linear custom VJP against the jnp reference."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hungarian import hungarian_min
from repro.fl import cohort as cohort_lib
from repro.fl import FLConfig, FLTrainer, Scenario, Simulation
from repro.fl.data import make_fl_dataset, sample_cohort_batch
from repro.fl.roles import Device, Gateway, fedavg
from repro.kernels.fused_linear import ops as fused_ops
from repro.kernels.fused_linear.ref import fused_linear_ref
from repro.models import split_model as sm

K_ITERS, LR = 3, 0.05


@pytest.fixture(scope="module")
def cohort_setup():
    n_dev, classes = 6, 10
    sizes = np.array([40, 52, 37, 64, 45, 58])
    d_tilde = np.array([8, 12, 7, 16, 9, 11])
    ds = make_fl_dataset(n_dev, sizes, np.full(n_dev, 3), classes=classes,
                         seed=3)
    plan = sm.MLPSplitModel(sizes=(3072, 64, 32, classes))
    params = plan.init(jax.random.PRNGKey(0))
    gws = [Gateway(0, [Device(0, 0, 40, 8), Device(1, 0, 52, 12),
                       Device(2, 0, 37, 7)]),
           Gateway(1, [Device(3, 1, 64, 16), Device(4, 1, 45, 9),
                       Device(5, 1, 58, 11)])]
    gw_onehot = np.zeros((n_dev, 2))
    gw_onehot[:3, 0] = gw_onehot[3:, 1] = 1.0
    return plan, params, ds, d_tilde, gws, gw_onehot


def _run_sequential(plan, params, ds, gws, trained, l_n, rng):
    models, weights, gw_losses = [], [], {}
    for m in trained:
        gw = gws[m]
        l_splits = np.asarray([l_n[d.idx] for d in gw.devices])
        combined, gw_loss, w_m = gw.shop_floor_round(
            plan, params, ds, l_splits, K_ITERS, LR, rng)
        models.append(combined)
        weights.append(w_m)
        gw_losses[m] = gw_loss
    return fedavg(models, np.asarray(weights, float)), gw_losses


def _run_cohort(plan, params, ds, d_tilde, gws, gw_onehot, trained, l_n, rng):
    device_ids, weights = [], np.zeros(len(d_tilde), np.float32)
    for m in trained:
        for dev in gws[m].devices:
            device_ids.append(dev.idx)
            weights[dev.idx] = dev.d_tilde
    batch = sample_cohort_batch(rng, ds, device_ids, d_tilde,
                                int(d_tilde.max()))
    return cohort_lib.cohort_round(plan, params, batch, l_n, weights,
                                   gw_onehot, K_ITERS, LR)


def test_cohort_round_matches_sequential(cohort_setup):
    """Same seeds, same l_n vector -> same global params and losses."""
    plan, params, ds, d_tilde, gws, gw_onehot = cohort_setup
    l_n = np.array([0, 1, 2, 3, 1, 2])
    trained = [0, 1]
    seq_params, seq_losses = _run_sequential(
        plan, params, ds, gws, trained, l_n, np.random.default_rng(42))
    new_params, gw_loss, gw_count, _, boundary = _run_cohort(
        plan, params, ds, d_tilde, gws, gw_onehot, trained, l_n,
        np.random.default_rng(42))
    for a, b in zip(jax.tree.leaves(new_params), jax.tree.leaves(seq_params)):
        np.testing.assert_allclose(a, b, atol=1e-5)
    for m in trained:
        assert float(gw_loss[m]) == pytest.approx(seq_losses[m], abs=1e-4)
    assert list(np.asarray(gw_count)) == [3.0, 3.0]
    assert np.asarray(boundary).shape == (6,)
    assert (np.asarray(boundary) > 0).all()      # all devices participated


def test_cohort_partial_participation_matches_sequential(cohort_setup):
    """Non-participating devices are zero-masked, not dropped: shapes stay
    fixed and the FedAvg only mixes participants."""
    plan, params, ds, d_tilde, gws, gw_onehot = cohort_setup
    l_n = np.array([2, 2, 2, 0, 0, 0])
    trained = [0]                                 # only gateway 0 trains
    seq_params, seq_losses = _run_sequential(
        plan, params, ds, gws, trained, l_n, np.random.default_rng(7))
    new_params, gw_loss, gw_count, _, _ = _run_cohort(
        plan, params, ds, d_tilde, gws, gw_onehot, trained, l_n,
        np.random.default_rng(7))
    for a, b in zip(jax.tree.leaves(new_params), jax.tree.leaves(seq_params)):
        np.testing.assert_allclose(a, b, atol=1e-5)
    assert float(gw_loss[0]) == pytest.approx(seq_losses[0], abs=1e-4)
    assert float(gw_count[1]) == 0.0


def test_cohort_compiles_once_across_varying_subsets(cohort_setup,
                                                     compile_count):
    """3 rounds with different device subsets and l_n vectors reuse one
    compiled executable (fixed-shape batching contract)."""
    plan, params, ds, d_tilde, gws, gw_onehot = cohort_setup
    rng = np.random.default_rng(0)
    with compile_count("trace.cohort.round") as c:
        for trained, l_n in [([0], [1, 2, 3, 0, 0, 0]),
                             ([1], [0, 0, 0, 1, 2, 3]),
                             ([0, 1], [3, 2, 1, 0, 1, 2])]:
            _run_cohort(plan, params, ds, d_tilde, gws, gw_onehot, trained,
                        np.asarray(l_n), rng)
    assert c.count <= 1


def test_cohort_round_matches_sequential_vgg():
    """Conv plans (no reshape-hoist fast path) agree too."""
    classes = 10
    sizes = np.array([40, 44])
    d_tilde = np.array([5, 7])
    ds = make_fl_dataset(2, sizes, np.full(2, 3), classes=classes, seed=5)
    plan = sm.VGGSplitModel(width_mult=0.06)
    params = plan.init(jax.random.PRNGKey(1))
    gws = [Gateway(0, [Device(0, 0, 40, 5), Device(1, 0, 44, 7)])]
    gw_onehot = np.ones((2, 1))
    l_n = np.array([4, 13])
    seq_params, seq_losses = _run_sequential(
        plan, params, ds, gws, [0], l_n, np.random.default_rng(11))
    new_params, gw_loss, _, _, boundary = _run_cohort(
        plan, params, ds, d_tilde, gws, gw_onehot, [0], l_n,
        np.random.default_rng(11))
    for a, b in zip(jax.tree.leaves(new_params), jax.tree.leaves(seq_params)):
        np.testing.assert_allclose(a, b, atol=2e-5)
    assert float(gw_loss[0]) == pytest.approx(seq_losses[0], abs=1e-4)
    assert (np.asarray(boundary) > 0).all()


def test_trainer_cohort_engine_matches_sequential_run():
    """Full FL loop: both engines produce the same trajectory."""
    cohort = FLTrainer(FLConfig(model="mlp", rounds=3, eval_every=3, seed=0,
                                engine="cohort")).run("ddsra")
    seq = FLTrainer(FLConfig(model="mlp", rounds=3, eval_every=3, seed=0,
                             engine="sequential")).run("ddsra")
    np.testing.assert_allclose(cohort.losses, seq.losses, atol=1e-3)
    assert abs(cohort.accuracy[-1] - seq.accuracy[-1]) < 0.02
    np.testing.assert_array_equal(cohort.participation, seq.participation)


def test_estimate_stats_cohort_matches_sequential():
    tr = FLTrainer(FLConfig(model="mlp", rounds=1, seed=1, engine="cohort"))
    params = tr.bs.params
    # re-seed the rng so both estimators sample identical batches
    tr.rng = np.random.default_rng(123)
    b = tr.estimate_stats(params, engine="cohort")
    tr.rng = np.random.default_rng(123)
    c = tr.estimate_stats(params, engine="sequential")
    np.testing.assert_allclose(b.sigma, c.sigma, rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(b.delta, c.delta, rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(b.lipschitz, c.lipschitz, rtol=1e-3, atol=1e-4)


# ---------------------------------------------------------------------------
# fused_linear custom VJP
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("act", ["none", "relu", "silu", "gelu"])
@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_fused_linear_custom_vjp_matches_ref_grads(act, impl):
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    x = jax.random.normal(keys[0], (8, 16))
    w = jax.random.normal(keys[1], (16, 8)) / 4.0
    b = jax.random.normal(keys[2], (8,))
    dy_seed = jax.random.normal(keys[3], (8, 8))

    def f_new(x, w, b):
        return jnp.sum(fused_ops.linear(x, w, b, activation=act, impl=impl)
                       * dy_seed)

    def f_ref(x, w, b):
        return jnp.sum(fused_linear_ref(x, w, b, act) * dy_seed)

    out_new = fused_ops.linear(x, w, b, activation=act, impl=impl)
    np.testing.assert_allclose(out_new, fused_linear_ref(x, w, b, act),
                               atol=1e-5, rtol=1e-5)
    g_new = jax.grad(f_new, argnums=(0, 1, 2))(x, w, b)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(x, w, b)
    for a, r in zip(g_new, g_ref):
        np.testing.assert_allclose(a, r, atol=1e-5, rtol=1e-5)


def test_fused_linear_custom_vjp_under_vmap():
    """The cohort engine vmaps the fc layers over devices."""
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    x = jax.random.normal(keys[0], (4, 8, 16))           # (devices, B, K)
    w = jax.random.normal(keys[1], (4, 16, 8)) / 4.0
    b = jax.random.normal(keys[2], (4, 8))

    def per_dev(x, w, b):
        return jnp.sum(fused_ops.linear(x, w, b, activation="relu",
                                        impl="ref"))

    g = jax.grad(lambda ws: jnp.sum(jax.vmap(per_dev, in_axes=(0, 0, 0))(
        x, ws, b)))(w)
    g_ref = jax.grad(lambda ws: jnp.sum(jax.vmap(
        lambda xx, ww, bb: jnp.sum(fused_linear_ref(xx, ww, bb, "relu")))(
            x, ws, b)))(w)
    np.testing.assert_allclose(g, g_ref, atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# hungarian: vectorized column scan vs brute force (no hypothesis needed)
# ---------------------------------------------------------------------------


def test_tokens_bf16_round_parity():
    """Mixed precision on the ``input_kind="tokens"`` data plane: int32
    token batches must pass through ``_cast_floats`` untouched (only
    float leaves — params, activations — drop to bf16), so a bf16
    transformer round agrees with its f32 twin at bf16-storage
    tolerance, and the control plane (selection, delays, queues) is
    bit-identical — compute dtype never leaks into scheduling. Upload
    bits are pinned (dtype="bf16" alone would price uploads at 16 bits
    and legitimately change the delays) so the only varying input IS the
    compute dtype."""
    def run(dtype):
        sc = Scenario(model="transformer", seq_len=8, rounds=2, k_iters=1,
                      eval_every=1, alpha=0.2, max_dataset=400, seed=0,
                      policy="ddsra_jax", engine="cohort", dtype=dtype,
                      upload_bits=32)
        sim = Simulation(sc)
        assert sim.plan.input_kind == "tokens"
        assert all(x.dtype == np.int32 for x in sim.ds.x_dev)
        recs = list(sim.rounds())
        return sim, recs

    sim32, recs32 = run("f32")
    sim16, recs16 = run("bf16")
    for a, b in zip(recs32, recs16):
        np.testing.assert_array_equal(b.selected, a.selected)
        assert list(b.trained) == list(a.trained)
        assert b.delay == pytest.approx(a.delay, rel=1e-12)
        np.testing.assert_allclose(b.queues, a.queues, atol=1e-12)
        # losses re-converge within bf16 resolution (~8 mantissa bits)
        np.testing.assert_allclose(
            np.asarray(b.losses), np.asarray(a.losses), rtol=0.05, atol=0.05)
        assert b.accuracy == pytest.approx(a.accuracy, abs=0.1)
    # master params stay f32 in both runs and drift only by bf16 rounding
    for l32, l16 in zip(jax.tree.leaves(sim32.params),
                        jax.tree.leaves(sim16.params)):
        assert l16.dtype == l32.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(l16), np.asarray(l32),
                                   rtol=0.1, atol=0.02)


def test_hungarian_vectorized_matches_bruteforce():
    rng = np.random.default_rng(0)
    for _ in range(60):
        r = int(rng.integers(1, 7))
        c = int(rng.integers(r, 7))
        cost = rng.uniform(0, 10, (r, c))
        col, total = hungarian_min(cost)
        assert len(set(col.tolist())) == r and (col >= 0).all()
        best = min(sum(cost[i, p[i]] for i in range(r))
                   for p in itertools.permutations(range(c), r))
        assert total == pytest.approx(best, abs=1e-9)
