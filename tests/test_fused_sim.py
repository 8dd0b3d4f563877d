"""The fused simulation loop vs the stepwise round loop.

Parity contract (pinned here, required by ``repro.fl.fused_sim``): across
{cohort, sharded} x {ddsra_jax, round_robin, delay_driven} x {f32, bf16}
x {host, traced} data planes, the fused path
reproduces the stepwise loop's RoundRecord stream and end state with
bit-identical queues and RNG streams (both the channel and the batch
stream) and params within atol 1e-5 — including when a checkpoint is saved
mid-run and resumed into either path. The seeds x V sweep matches per-seed
stepwise loops row-for-row, deterministically across processes; the fused
run is one decide compile + one train compile, with zero retraces when
only values change; and the RoundTelemetry pytree round-trips exactly.
"""
import dataclasses
import hashlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax

from repro.core.network import NetworkConfig
from repro.fl import fused_sim
from repro.fl.fused_sim import RoundTelemetry
from repro.fl.sim import RoundRecord, Scenario, Simulation

_BASE = dict(model="mlp", alpha=0.2, max_dataset=120, rounds=5, k_iters=2,
             eval_every=100, net=NetworkConfig(3, 9, 2))


def _scenario(**over):
    return Scenario(**{**_BASE, **over})


def _run_stepwise(sc, n=None):
    sim = Simulation(sc)
    gen = sim.rounds()
    recs = [next(gen) for _ in range(sc.rounds if n is None else n)]
    return sim, recs


def _assert_record_parity(recs_a, recs_b, *, loss_atol=1e-5):
    assert len(recs_a) == len(recs_b)
    for a, b in zip(recs_a, recs_b):
        assert a.t == b.t
        assert np.array_equal(a.selected, b.selected), a.t
        assert a.trained == b.trained, a.t
        assert np.array_equal(a.l_n, b.l_n), a.t
        assert a.delay == pytest.approx(b.delay, rel=1e-12), a.t
        assert a.cum_delay == pytest.approx(b.cum_delay, rel=1e-12), a.t
        assert np.array_equal(a.queues, b.queues), a.t      # bit-identical
        np.testing.assert_allclose(b.losses, a.losses, atol=loss_atol)
        assert a.failures == b.failures, a.t
        assert a.aggregations == b.aggregations, a.t


def _assert_end_state_parity(sim_a, sim_b, *, atol=1e-5):
    # bit-identical queues and BOTH RNG streams; params to atol
    assert np.array_equal(sim_a.queues, sim_b.queues)
    assert sim_a.rng.bit_generator.state == sim_b.rng.bit_generator.state
    assert sim_a.net.rng.bit_generator.state == \
        sim_b.net.rng.bit_generator.state
    assert sim_a.t == sim_b.t
    assert sim_a.delay_sum == pytest.approx(sim_b.delay_sum, rel=1e-12)
    for a, b in zip(jax.tree.leaves(sim_a.params),
                    jax.tree.leaves(sim_b.params)):
        np.testing.assert_allclose(np.asarray(a, np.float64),
                                   np.asarray(b, np.float64), atol=atol)


# ---------------------------------------------------------------------------
# the parity matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ["cohort", "sharded"])
@pytest.mark.parametrize("policy", ["ddsra_jax", "round_robin",
                                    "delay_driven"])
@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_fused_matches_stepwise(engine, policy, dtype):
    sc = _scenario(engine=engine, policy=policy, dtype=dtype)
    sim_a, recs_a = _run_stepwise(sc)
    sim_b = Simulation(sc)
    recs_b = sim_b.fused_rounds()
    _assert_record_parity(recs_a, recs_b)
    _assert_end_state_parity(sim_a, sim_b)


def _assert_accuracy_parity(recs_a, recs_b):
    for a, b in zip(recs_a, recs_b):
        assert (a.accuracy is None) == (b.accuracy is None), a.t
        if a.accuracy is not None:
            assert b.accuracy == pytest.approx(a.accuracy, abs=1e-6), a.t


@pytest.mark.parametrize("engine", ["cohort", "sharded"])
def test_fused_in_scan_eval_matches_stepwise(engine):
    """``eval_every`` accuracy snapshots run lax.cond-gated inside the
    train scan and equal the stepwise loop's post-round evals round for
    round — mid-run rounds included, not just the final one."""
    sc = _scenario(policy="ddsra_jax", engine=engine, eval_every=2)
    _, recs_a = _run_stepwise(sc)
    recs_b = Simulation(sc).fused_rounds()
    # the stepwise schedule: rounds where (t+1) % eval_every == 0, plus
    # the final round
    assert [r.t for r in recs_b if r.accuracy is not None] == [1, 3, 4]
    _assert_accuracy_parity(recs_a, recs_b)


@pytest.mark.parametrize("engine", ["cohort", "sharded"])
@pytest.mark.parametrize("policy", ["ddsra_jax", "delay_driven"])
def test_fused_matches_stepwise_traced_data_plane(engine, policy):
    """The traced data plane: counter-based jax batch draws gathered from
    device-resident stacks *inside* the train scan reproduce the stepwise
    loop (whose host oracle, ``sample_cohort_batch_traced``, derives the
    identical indices eagerly) — bit-identical queues/RNG, params at 1e-5,
    and identical in-scan accuracy snapshots."""
    sc = _scenario(engine=engine, policy=policy, data_plane="traced",
                   eval_every=2)
    sim_a, recs_a = _run_stepwise(sc)
    sim_b = Simulation(sc)
    recs_b = sim_b.fused_rounds()
    _assert_record_parity(recs_a, recs_b)
    _assert_accuracy_parity(recs_a, recs_b)
    _assert_end_state_parity(sim_a, sim_b)


def test_traced_plane_refused_off_cohort_engines():
    with pytest.raises(ValueError, match="data_plane"):
        Simulation(_scenario(engine="sequential", data_plane="traced"))


def test_traced_draws_byte_identical_to_resident_stack_gather():
    """The host oracle (``sample_cohort_batch_traced``) and the fused
    scan's in-program gather read the SAME bytes: every occupied slot's
    valid rows equal a direct gather of ``traced_batch_indices`` into the
    device-resident stacks, and a wider slot's draw extends a narrower
    one's (the prefix property the tiered widths rely on)."""
    from repro.fl.data import (device_resident_stacks,
                               sample_cohort_batch_traced,
                               traced_batch_indices)
    sim = Simulation(_scenario(data_plane="traced", tiers=2))
    layout = sim.engine._layout(sim, sim.cohort_capacity)
    x_all, y_all, pool = device_resident_stacks(sim.ds)
    l_max = x_all.shape[1]
    key = sim.data_key
    device_ids = list(range(min(sim.cohort_capacity,
                                sim.net.cfg.n_devices)))
    for t in (0, 3):
        batch = sample_cohort_batch_traced(key, t, sim.ds, device_ids,
                                           sim.d_tilde, layout)
        for di, n in enumerate(device_ids):
            k, row = layout.locate(int(batch.slot_of[di]))
            width = layout.tier_widths[k]
            b = int(min(sim.d_tilde[n], pool[n]))
            idx = np.asarray(traced_batch_indices(
                key, t, n, int(pool[n]), width, l_max))
            # prefix property: the width-draw's first b indices ARE the
            # b-draw (so any tier width reads the same b valid rows)
            idx_b = np.asarray(traced_batch_indices(
                key, t, n, int(pool[n]), b, l_max))
            assert np.array_equal(idx[:b], idx_b)
            assert batch.tiers[k].x[row, :b].tobytes() == \
                x_all[n][idx[:b]].tobytes()
            assert batch.tiers[k].y[row, :b].tobytes() == \
                y_all[n][idx[:b]].tobytes()
            assert batch.tiers[k].mask[row, :b].all()
            assert not batch.tiers[k].mask[row, b:].any()


def test_fused_and_stepwise_blocks_interleave():
    """End-state parity is strong enough to mix the two paths mid-run."""
    sc = _scenario(rounds=6)
    sim_a, recs_a = _run_stepwise(sc)
    sim_b = Simulation(sc)
    recs_b = sim_b.fused_rounds(rounds=3)          # fused block ...
    gen = sim_b.rounds()
    recs_b += [next(gen) for _ in range(2)]        # ... stepwise block ...
    recs_b += sim_b.fused_rounds(rounds=1)         # ... fused again
    _assert_record_parity(recs_a, recs_b)
    _assert_end_state_parity(sim_a, sim_b)


def test_fused_resume_from_checkpoint_mid_sweep(tmp_path):
    """A checkpoint saved after a fused block resumes bit-identically into
    both the fused and the stepwise path."""
    sc = _scenario(rounds=6, policy="ddsra_jax")
    sim = Simulation(sc)
    recs = sim.fused_rounds(rounds=3)
    sim.save(tmp_path, block=True)
    recs_a = recs + sim.fused_rounds()             # finish fused, in-place

    sim_f = Simulation.resume(tmp_path)            # resume -> fused
    recs_f = recs[:3] + sim_f.fused_rounds()
    _assert_record_parity(recs_a, recs_f)
    _assert_end_state_parity(sim, sim_f, atol=0.0)  # same path: exact

    sim_s = Simulation.resume(tmp_path)            # resume -> stepwise
    gen = sim_s.rounds()
    recs_s = recs[:3] + [next(gen) for _ in range(3)]
    _assert_record_parity(recs_a, recs_s)
    _assert_end_state_parity(sim, sim_s)


# ---------------------------------------------------------------------------
# refusals
# ---------------------------------------------------------------------------


def test_fused_refuses_loss_driven_policy():
    sim = Simulation(_scenario(policy="loss_driven"))
    with pytest.raises(ValueError, match="reads_losses"):
        sim.fused_rounds()
    # the refusal happened before any stream was consumed
    assert sim.net.rng.bit_generator.state == sim._net_rng_state0


def test_fused_refuses_async_engine():
    sim = Simulation(_scenario(engine="async"))
    with pytest.raises(NotImplementedError, match="async"):
        sim.fused_rounds()
    assert sim.net.rng.bit_generator.state == sim._net_rng_state0


def test_sweep_requires_traced_decide_policy():
    sim = Simulation(_scenario(policy="loss_driven"))
    with pytest.raises(ValueError, match="traced-decide"):
        sim.sweep([0.01, 1.0])


def test_sweep_refuses_fixed_resource_baselines():
    # round_robin decides traced now, but a V sweep over it is meaningless:
    # fixed-resource baselines never read V
    sim = Simulation(_scenario(policy="round_robin"))
    with pytest.raises(ValueError, match="V-sweep"):
        sim.sweep([0.01, 1.0])


# ---------------------------------------------------------------------------
# compile-count / retrace regression
# ---------------------------------------------------------------------------


def test_fused_run_is_two_compiles_and_never_retraces(compile_count):
    """One decide-scan trace + one train-scan trace for an N-round fused
    run; a second run over the same shapes (different seed, so different
    values everywhere) retraces nothing."""
    sc = _scenario(policy="ddsra_jax")
    Simulation(sc).fused_rounds()                  # warm (or cached)
    with compile_count("trace.ddsra.decide", "trace.ddsra.round",
                       "trace.cohort.train_scan", "trace.cohort.round") as c:
        sim = Simulation(sc)
        sim.reset(seed=123)
        sim.fused_rounds()
    assert c.count == 0


def test_sweep_is_one_compile_across_value_changes(compile_count):
    """The seeds x V sweep compiles once; changing the seeds and V values
    (same counts) re-runs the same executable."""
    sim = Simulation(_scenario(policy="ddsra_jax"))
    sim.sweep([0.01, 1.0], seeds=[0, 1], rounds=4)           # warm
    with compile_count("trace.ddsra.sweep") as c:
        res = sim.sweep([0.5, 50.0], seeds=[3, 9], rounds=4)
    assert c.count == 0
    assert res.taus.shape == (2, 2, 4)


_POLICIES = ["ddsra_jax", "round_robin", "random", "delay_driven"]


def test_multi_policy_sweep_is_one_program(compile_count):
    """The whole policies x seeds x V grid is ONE compiled program — not
    one per policy — and changing values (seeds, V) never retraces."""
    sim = Simulation(_scenario(policy="ddsra_jax"))
    sim.sweep([0.01, 1.0], seeds=[0, 1], rounds=4, policies=_POLICIES)
    with compile_count("trace.policy_sweep.sweep") as c:
        res = sim.sweep([0.5, 50.0], seeds=[3, 9], rounds=4,
                        policies=_POLICIES)
    assert c.count == 0
    assert res.taus.shape == (4, 2, 2, 4)
    assert res.policies == _POLICIES


def test_multi_policy_sweep_refuses_host_policies():
    sim = Simulation(_scenario(policy="ddsra_jax"))
    with pytest.raises(ValueError, match="loss_driven"):
        sim.sweep([0.01], rounds=2, policies=["ddsra_jax", "loss_driven"])


# ---------------------------------------------------------------------------
# seeds x V sweep determinism
# ---------------------------------------------------------------------------


def _sweep_digest(res) -> str:
    h = hashlib.sha256()
    for a in (res.taus, res.selected, res.queues):
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def test_sweep_matches_stepwise_rows():
    """Every (seed, v) sweep lane equals the stepwise reset(seed) run at
    that V, row for row: realized delays, participation, queues."""
    sc = _scenario(policy="ddsra_jax")
    sim = Simulation(sc)
    res = sim.sweep([0.01, 10.0], seeds=[0, 7], rounds=4)
    for si, seed in enumerate(res.seeds):
        for vi, v in enumerate(res.v_values):
            ref = Simulation(dataclasses.replace(sc, v=v, rounds=4))
            ref.reset(seed)
            recs = list(ref.rounds())
            np.testing.assert_allclose(
                res.taus[si, vi], [r.delay for r in recs], rtol=1e-9)
            assert np.array_equal(
                res.selected[si, vi],
                np.asarray([r.selected for r in recs]))
            np.testing.assert_allclose(
                res.queues[si, vi],
                np.asarray([r.queues for r in recs]), atol=1e-12)


def test_multi_policy_sweep_matches_stepwise_rows():
    """Every (policy, seed, v) lane of the one-program grid equals the
    stepwise ``reset(seed)`` run of that policy at that V, row for row:
    realized delays, participation, and bit-exact queue recursions —
    including the delay_driven lane, whose greedy pick is computed
    in-scan from the round's channel draws."""
    sc = _scenario(policy="ddsra_jax")
    sim = Simulation(sc)
    res = sim.sweep([0.01, 10.0], seeds=[0, 7], rounds=4,
                    policies=_POLICIES)
    for pi, pol in enumerate(_POLICIES):
        for si, seed in enumerate(res.seeds):
            for vi, v in enumerate(res.v_values):
                ref = Simulation(dataclasses.replace(
                    sc, v=v, rounds=4, policy=pol))
                ref.reset(seed)
                recs = list(ref.rounds())
                np.testing.assert_allclose(
                    res.taus[pi, si, vi], [r.delay for r in recs],
                    rtol=1e-9, err_msg=f"{pol} seed={seed} v={v}")
                assert np.array_equal(
                    res.selected[pi, si, vi],
                    np.asarray([r.selected for r in recs])), (pol, seed, v)
                np.testing.assert_allclose(
                    res.queues[pi, si, vi],
                    np.asarray([r.queues for r in recs]), atol=1e-12,
                    err_msg=f"{pol} seed={seed} v={v}")
    # fixed-resource lanes never read V: identical rows across the V axis
    for pi, pol in enumerate(_POLICIES):
        if pol != "ddsra_jax":
            assert np.array_equal(res.taus[pi, :, 0], res.taus[pi, :, 1])


_SWEEP_SCRIPT = textwrap.dedent("""
    import hashlib, numpy as np
    from repro.core.network import NetworkConfig
    from repro.fl.sim import Scenario, Simulation
    sc = Scenario(model="mlp", alpha=0.2, max_dataset=120, rounds=5,
                  k_iters=2, eval_every=100, policy="ddsra_jax",
                  net=NetworkConfig(3, 9, 2))
    sim = Simulation(sc)
    for pols in (None, ["ddsra_jax", "round_robin", "random",
                        "delay_driven"]):
        res = sim.sweep([0.01, 10.0], seeds=[0, 7], rounds=4,
                        policies=pols)
        h = hashlib.sha256()
        for a in (res.taus, res.selected, res.queues):
            h.update(np.ascontiguousarray(a).tobytes())
        print(h.hexdigest())
""")


def test_sweep_deterministic_across_processes():
    """The same sweeps — the classic seeds x V grid and the multi-policy
    grid — in a fresh interpreter produce byte-identical trajectories
    (no hash seeds, no device-order dependence)."""
    sim = Simulation(_scenario(policy="ddsra_jax"))
    local = _sweep_digest(sim.sweep([0.01, 10.0], seeds=[0, 7], rounds=4))
    local_mp = _sweep_digest(sim.sweep([0.01, 10.0], seeds=[0, 7], rounds=4,
                                       policies=_POLICIES))
    out = subprocess.run([sys.executable, "-c", _SWEEP_SCRIPT],
                         capture_output=True, text=True, check=True)
    assert out.stdout.strip().splitlines() == [local, local_mp]


# ---------------------------------------------------------------------------
# RoundTelemetry pytree properties
# ---------------------------------------------------------------------------


def _random_telemetry(rng, t, m, n) -> RoundTelemetry:
    trained = rng.random((t, m)) < 0.5
    aggs = trained.any(axis=1).astype(int)
    delay = np.where(aggs > 0, rng.random(t), 0.0)
    return RoundTelemetry(
        t=np.arange(t), selected=rng.random((t, m)) < 0.7, trained=trained,
        l_n=rng.integers(0, 4, (t, n)), delay=delay,
        cum_delay=np.cumsum(delay), queues=rng.random((t, m)),
        losses=rng.random((t, m)), failures=rng.integers(0, 2, t),
        aggregations=aggs,
        staleness_mean=np.where(aggs > 0, rng.random(t), 0.0),
        staleness_max=np.zeros(t, int), stale_discarded=np.zeros(t, int),
        dropped_devices=np.zeros(t, int), lost_devices=np.zeros(t, int),
        straggler_devices=np.zeros(t, int), buffer_fill=np.zeros(t, int),
        inflight=np.zeros(t, int))


def _check_telemetry_invariants(tel: RoundTelemetry):
    # flatten -> unflatten is the identity (a well-formed pytree)
    leaves, treedef = jax.tree.flatten(tel)
    tel2 = jax.tree.unflatten(treedef, leaves)
    for a, b in zip(tel, tel2):
        assert a is b
    # a lax.scan round-trip re-emits every leaf unchanged (the stacked
    # telemetry really is scan-shaped: leading round axis everywhere).
    # x64 on: the control-plane leaves are float64 and must survive.
    with jax.enable_x64(True):
        carried = jax.lax.scan(lambda c, x: (c, x), 0, tel)[1]
    for a, b in zip(tel, carried):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # records round-trip exactly, with no tracers leaking to the host
    recs = tel.to_records()
    assert all(isinstance(r.delay, float) and isinstance(r.failures, int)
               for r in recs)
    assert all(isinstance(r.queues, np.ndarray) and
               not isinstance(r.queues, jax.Array) for r in recs)
    back = RoundTelemetry.from_records(recs)
    for name, a, b in zip(RoundTelemetry._fields, tel, back):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)
    # non-aggregating rounds carry exact zeros, never NaN
    quiet = np.asarray(tel.aggregations) == 0
    assert np.isfinite(np.asarray(tel.staleness_mean)).all()
    assert (np.asarray(tel.delay)[quiet] == 0.0).all()
    assert (np.asarray(tel.staleness_mean)[quiet] == 0.0).all()


def test_telemetry_pytree_roundtrip_fixed_seeds():
    """Deterministic version of the property test (runs without
    hypothesis)."""
    for seed in range(5):
        rng = np.random.default_rng(seed)
        _check_telemetry_invariants(
            _random_telemetry(rng, t=int(rng.integers(1, 8)),
                              m=int(rng.integers(1, 5)),
                              n=int(rng.integers(1, 9))))


def test_telemetry_pytree_properties_hypothesis():
    pytest.importorskip("hypothesis")  # container may lack hypothesis
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), t=st.integers(1, 10),
           m=st.integers(1, 6), n=st.integers(1, 12))
    def prop(seed, t, m, n):
        rng = np.random.default_rng(seed)
        _check_telemetry_invariants(_random_telemetry(rng, t, m, n))

    prop()


def test_telemetry_from_real_records():
    """from_records over a real stepwise stream rebuilds the fused stream's
    mask form and back."""
    _, recs = _run_stepwise(_scenario(policy="ddsra_jax"))
    tel = RoundTelemetry.from_records(recs)
    back = tel.to_records()
    for a, b in zip(recs, back):
        assert a.t == b.t and a.trained == b.trained
        assert np.array_equal(a.queues, b.queues)
        assert a.delay == pytest.approx(b.delay)
