"""Sharded cohort engine + tiered packing: mesh fallback, numerical parity
with the single-host cohort engine (round, shop-floor/gateway-model and
stats paths), the tiered slot-packing contract, and the public-API
docstring guarantee. An 8-way forced-host-device CPU mesh is exercised in a
subprocess so the parity contract holds in every environment (the CI matrix
additionally runs the whole suite under that flag)."""
import dataclasses
import inspect
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

import repro.fl as fl
from repro.core.network import NetworkConfig
from repro.fl import (CohortLayout, Scenario, Simulation, TieredCohortBatch,
                      make_engine)
from repro.fl import cohort as cohort_lib
from repro.fl.data import make_fl_dataset, sample_batch, sample_cohort_batch
from repro.fl.shard import ShardedCohortEngine
from repro.sharding import COHORT_AXIS, cohort_mesh


def _scenario(**kw):
    base = dict(model="mlp", rounds=3, eval_every=3, seed=0)
    base.update(kw)
    return Scenario(**base)


# ---------------------------------------------------------------------------
# mesh
# ---------------------------------------------------------------------------


def test_cohort_mesh_clamps_to_available_devices():
    """Asking for a bigger mesh than the host has raises instead of
    shrinking to the devices that exist; ``None`` takes every device."""
    with pytest.raises(ValueError, match="needs 4096 devices"):
        cohort_mesh((4096,))
    with pytest.raises(ValueError, match="needs"):
        cohort_mesh((len(jax.devices()) + 1,))
    mesh = cohort_mesh(None)
    assert mesh.axis_names == (COHORT_AXIS,)
    assert mesh.shape[COHORT_AXIS] == len(jax.devices())
    assert cohort_mesh((1,)).shape[COHORT_AXIS] == 1


def test_sharded_engine_registered():
    eng = make_engine("sharded")
    assert isinstance(eng, ShardedCohortEngine)
    assert Scenario(engine="sharded").engine == "sharded"


# ---------------------------------------------------------------------------
# tiered slot layout / packing contract
# ---------------------------------------------------------------------------


def test_layout_tiers_partition_capacity_and_respect_shard_count():
    d_tilde = np.array([17, 3, 9, 5, 8, 2, 13, 11])
    for tiers in (1, 2, 3, 8, 20):
        for shards in (1, 2, 3):
            lay = CohortLayout.build(d_tilde, capacity=6, tiers=tiers,
                                     shard_count=shards)
            assert all(s % shards == 0 for s in lay.tier_slots)
            assert lay.n_slots >= 6
            widths = lay.slot_widths
            assert (np.diff(widths) <= 0).all()          # non-increasing
            assert widths[0] == 17                       # global max first
            assert lay.padded_samples == widths.sum()
    # tiers=1, shard_count=1 reproduces the single-width contract exactly
    lay = CohortLayout.build(d_tilde, capacity=6)
    assert lay.tier_widths == (17,) and lay.tier_slots == (6,)


def test_tiered_layout_cuts_padded_samples():
    rng = np.random.default_rng(0)
    d_tilde = rng.integers(4, 60, size=64)
    flat = CohortLayout.build(d_tilde, capacity=32, tiers=1)
    tiered = CohortLayout.build(d_tilde, capacity=32, tiers=4)
    assert tiered.padded_samples < flat.padded_samples


def test_auto_tiers_never_pads_more_than_manual_baselines():
    """tiers="auto" on the bench layouts ({20, 64, 128} devices, the
    fl_round_bench d_tilde distribution) must never pad more samples than
    the manual 1- and 4-tier baselines, for unsharded and mesh-8 layouts."""
    for n in (20, 64, 128):
        rng = np.random.default_rng(1)            # Simulation's seed + 1
        d_sizes = np.maximum(rng.uniform(0, 2000, n).astype(int), 40)
        d_tilde = np.maximum((0.05 * d_sizes).astype(int), 4)
        for shards in (1, 8):
            auto = CohortLayout.build(d_tilde, tiers="auto",
                                      shard_count=shards)
            for manual in (1, 4):
                base = CohortLayout.build(d_tilde, tiers=manual,
                                          shard_count=shards)
                assert auto.padded_samples <= base.padded_samples, \
                    (n, shards, manual)
            assert 1 <= len(auto.tier_widths) <= CohortLayout.AUTO_MAX_TIERS


def test_auto_tiers_property():
    """Random d_tilde/capacity/shard_count: auto is the best candidate
    count (<= every manual choice up to AUTO_MAX_TIERS) and a valid int."""
    rng = np.random.default_rng(5)
    for _ in range(25):
        n = int(rng.integers(4, 40))
        d_tilde = rng.integers(4, 120, size=n)
        capacity = int(rng.integers(1, n + 1))
        shards = int(rng.integers(1, 4))
        t_auto = CohortLayout.auto_tiers(d_tilde, capacity, shards)
        auto = CohortLayout.build(d_tilde, capacity, "auto", shards)
        assert auto == CohortLayout.build(d_tilde, capacity, t_auto, shards)
        top = min(capacity, CohortLayout.AUTO_MAX_TIERS)
        for manual in range(1, top + 1):
            base = CohortLayout.build(d_tilde, capacity, manual, shards)
            assert auto.padded_samples <= base.padded_samples


def test_tiered_packing_property():
    """Every participating device's real samples land in exactly one slot;
    mask totals equal the true drawn batch sizes; empty slots stay empty."""
    n_dev = 9
    sizes = np.array([40, 22, 37, 64, 45, 18, 52, 33, 26])
    d_tilde = np.array([12, 5, 9, 16, 11, 4, 14, 8, 6])
    ds = make_fl_dataset(n_dev, sizes, np.full(n_dev, 3), seed=2)
    rng0 = np.random.default_rng(0)
    for trial in range(6):
        tiers = int(rng0.integers(1, 5))
        shards = int(rng0.integers(1, 4))
        k = int(rng0.integers(1, 8))
        ids = rng0.choice(n_dev, size=k, replace=False).tolist()
        layout = CohortLayout.build(d_tilde, capacity=7, tiers=tiers,
                                    shard_count=shards)
        batch = sample_cohort_batch(np.random.default_rng(trial), ds, ids,
                                    d_tilde, layout=layout)
        assert isinstance(batch, TieredCohortBatch)
        # slot assignment is injective and in-range
        assert len(set(batch.slot_of.tolist())) == len(ids)
        assert (batch.slot_of >= 0).all()
        assert (batch.slot_of < layout.n_slots).all()
        mask_by_slot = np.concatenate(
            [t.mask.sum(axis=1) for t in batch.tiers])
        widths = layout.slot_widths
        for di, n in enumerate(ids):
            drawn = min(int(d_tilde[n]), int(sizes[n]))
            s = int(batch.slot_of[di])
            assert mask_by_slot[s] == drawn          # all samples, one slot
            assert drawn <= widths[s]                # slot is wide enough
        # unassigned slots hold nothing; totals match the true batch sizes
        unused = np.setdiff1d(np.arange(layout.n_slots), batch.slot_of)
        assert (mask_by_slot[unused] == 0).all()
        assert mask_by_slot.sum() == sum(
            min(int(d_tilde[n]), int(sizes[n])) for n in ids)


def test_tiered_packing_draws_match_sequential_order():
    """rng parity: the tiered path must consume the generator exactly as
    the sequential per-device loop does, in device_ids order."""
    n_dev = 6
    sizes = np.array([40, 52, 37, 64, 45, 58])
    d_tilde = np.array([8, 12, 7, 16, 9, 11])
    ds = make_fl_dataset(n_dev, sizes, np.full(n_dev, 3), seed=3)
    ids = [4, 1, 5, 2]
    layout = CohortLayout.build(d_tilde, capacity=5, tiers=3)
    batch = sample_cohort_batch(np.random.default_rng(7), ds, ids, d_tilde,
                                layout=layout)
    rng = np.random.default_rng(7)
    for di, n in enumerate(ids):
        xb, yb = sample_batch(rng, ds, n, int(d_tilde[n]))
        k, row = layout.locate(int(batch.slot_of[di]))
        t = batch.tiers[k]
        np.testing.assert_array_equal(t.x[row, :len(yb)], xb)
        np.testing.assert_array_equal(t.y[row, :len(yb)], yb)
        assert t.mask[row].sum() == len(yb)


def test_tiered_cohort_round_matches_single_width():
    """The fused round over a tiered batch equals the single-width batch
    round (same devices, same draws) at atol 1e-5."""
    n_dev = 6
    sizes = np.array([40, 52, 37, 64, 45, 58])
    d_tilde = np.array([8, 12, 7, 16, 9, 11])
    ds = make_fl_dataset(n_dev, sizes, np.full(n_dev, 3), seed=3)
    from repro.models import split_model as sm
    plan = sm.MLPSplitModel(sizes=(3072, 64, 32, 10))
    params = plan.init(jax.random.PRNGKey(0))
    ids = [0, 1, 2, 3, 4, 5]
    gw_of = np.array([0, 0, 0, 1, 1, 1])
    l_n = np.array([0, 1, 2, 3, 1, 2])

    flat = sample_cohort_batch(np.random.default_rng(42), ds, ids, d_tilde,
                               int(d_tilde.max()), capacity=6)
    onehot = np.zeros((6, 2), np.float32)
    onehot[np.arange(6), gw_of] = 1.0
    ref = cohort_lib.cohort_round(plan, params, flat, l_n,
                                  d_tilde.astype(np.float32), onehot, 3, 0.05)

    layout = CohortLayout.build(d_tilde, capacity=6, tiers=3)
    tiered = sample_cohort_batch(np.random.default_rng(42), ds, ids, d_tilde,
                                 layout=layout)
    s = layout.n_slots
    l_slot, w_slot = np.zeros(s, int), np.zeros(s, np.float32)
    oh_slot = np.zeros((s, 2), np.float32)
    for di, n in enumerate(ids):
        sl = int(tiered.slot_of[di])
        l_slot[sl], w_slot[sl] = l_n[n], d_tilde[n]
        oh_slot[sl, gw_of[n]] = 1.0
    got = cohort_lib.cohort_round(plan, params, tiered, l_slot, w_slot,
                                  oh_slot, 3, 0.05)
    for a, b in zip(jax.tree.leaves(got[0]), jax.tree.leaves(ref[0])):
        np.testing.assert_allclose(a, b, atol=1e-5)
    np.testing.assert_allclose(got[1], ref[1], atol=1e-4)   # gateway losses
    # per-slot boundary RMS maps back to the same per-device values
    np.testing.assert_allclose(np.asarray(got[4])[tiered.slot_of],
                               np.asarray(ref[4]), atol=1e-5)


# ---------------------------------------------------------------------------
# sharded engine parity (whatever mesh this host provides; 8-way in CI)
# ---------------------------------------------------------------------------


def _aligned_pair(sc):
    """(cohort sim, sharded sim) sharing stats and batch-RNG state, so both
    runs see identical data, channel draws and scheduling decisions."""
    ref = Simulation(dataclasses.replace(sc, engine="cohort"))
    shd = Simulation(dataclasses.replace(sc, engine="sharded"),
                     _stats=ref.stats)
    shd.rng.bit_generator.state = ref._rng_state0
    return ref, shd


def test_sharded_run_matches_cohort():
    sc = _scenario(tiers=2, net=NetworkConfig(n_gateways=4, n_devices=16,
                                              n_channels=4))
    ref, shd = _aligned_pair(sc)
    r1, r2 = ref.run("ddsra"), shd.run("ddsra")
    np.testing.assert_array_equal(r1.participation, r2.participation)
    np.testing.assert_allclose(r1.losses, r2.losses, atol=1e-5)
    assert r1.accuracy[-1] == pytest.approx(r2.accuracy[-1], abs=0.02)
    for a, b in zip(jax.tree.leaves(ref.params), jax.tree.leaves(shd.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    # both engines trained on the same padded-slot area
    assert ref.padding_stats["real_samples"] == \
        shd.padding_stats["real_samples"]


def test_sharded_compiles_once_across_rounds(compile_count):
    sc = _scenario(rounds=4, tiers=2)
    with compile_count("trace.shard.round") as c:
        Simulation(sc_sharded := dataclasses.replace(sc, engine="sharded"))
        Simulation(sc_sharded).run("ddsra")
    assert c.count <= 1


def test_sharded_shop_floor_round_matches_cohort():
    """The masked-psum gateway models equal the single-host fused ones,
    including when the all-device row count does not divide the mesh."""
    sim = Simulation(_scenario(rounds=1))
    ids = [d.idx for gw in sim.gateways for d in gw.devices]
    l_n = np.full(sim.net.cfg.n_devices, sim.plan.n_blocks // 2, int)
    a = sim.engine.shop_floor_round(sim, ids, l_n,
                                    rng=np.random.default_rng(3))
    b = make_engine("sharded").shop_floor_round(
        sim, ids, l_n, rng=np.random.default_rng(3))
    for x, y in zip(jax.tree.leaves(a[0]), jax.tree.leaves(b[0])):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-5)
    for x, y in zip(jax.tree.leaves(a[1]), jax.tree.leaves(b[1])):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-5)
    np.testing.assert_allclose(a[2], b[2], atol=1e-4)


def test_sharded_estimate_stats_matches_cohort():
    sim = Simulation(_scenario(rounds=1))
    sim.rng = np.random.default_rng(5)
    a = sim.estimate_stats(engine="cohort")
    sim.rng = np.random.default_rng(5)
    b = sim.estimate_stats(engine="sharded")
    np.testing.assert_allclose(a.sigma, b.sigma, rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(a.delta, b.delta, rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(a.lipschitz, b.lipschitz, rtol=1e-3, atol=1e-4)


_MESH8_SCRIPT = textwrap.dedent("""
    import dataclasses
    import numpy as np, jax
    assert len(jax.devices()) == 8, len(jax.devices())
    from repro.core.network import NetworkConfig
    from repro.fl import Scenario, Simulation
    from repro.sharding import COHORT_AXIS, cohort_mesh
    assert cohort_mesh(None).shape[COHORT_AXIS] == 8
    sc = Scenario(model="mlp", rounds=2, eval_every=2, seed=0, tiers=2,
                  net=NetworkConfig(n_gateways=4, n_devices=16, n_channels=4))
    ref = Simulation(dataclasses.replace(sc, engine="cohort"))
    shd = Simulation(dataclasses.replace(sc, engine="sharded"),
                     _stats=ref.stats)
    shd.rng.bit_generator.state = ref._rng_state0
    r1, r2 = ref.run("ddsra"), shd.run("ddsra")
    np.testing.assert_array_equal(r1.participation, r2.participation)
    np.testing.assert_allclose(r1.losses, r2.losses, atol=1e-5)
    for a, b in zip(jax.tree.leaves(ref.params), jax.tree.leaves(shd.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    print("MESH8_PARITY_OK")
""")


def test_sharded_parity_on_forced_8_device_mesh():
    """The headline contract: ShardedCohortEngine == CohortEngine at atol
    1e-5 on a real 8-way mesh (forced host devices; subprocess because
    XLA_FLAGS must be set before jax is imported)."""
    if len(jax.devices()) >= 8:
        pytest.skip("already on a multi-device host; covered in-process")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH",
                                                              ""))
    proc = subprocess.run([sys.executable, "-c", _MESH8_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "MESH8_PARITY_OK" in proc.stdout


# ---------------------------------------------------------------------------
# docs can't rot: every public repro.fl symbol is documented
# ---------------------------------------------------------------------------


def test_public_api_has_docstrings():
    import repro.fl.cohort
    import repro.fl.data
    import repro.fl.shard
    import repro.fl.sim
    for mod in (fl, repro.fl.sim, repro.fl.cohort, repro.fl.shard,
                repro.fl.data):
        assert (mod.__doc__ or "").strip(), mod.__name__
    for name in fl.__all__:
        obj = getattr(fl, name)
        if inspect.isclass(obj) or inspect.isfunction(obj):
            assert (obj.__doc__ or "").strip(), f"{name} lacks a docstring"
            if inspect.isclass(obj):
                for mname, raw in vars(obj).items():
                    if mname.startswith("_"):
                        continue
                    if not (inspect.isfunction(raw)
                            or isinstance(raw, (classmethod, staticmethod))):
                        continue
                    fn = raw.__func__ \
                        if isinstance(raw, (classmethod, staticmethod)) \
                        else raw
                    assert (fn.__doc__ or "").strip(), \
                        f"{name}.{mname} lacks a docstring"
