"""Cohort engine vs the seed sequential path: 10-round, 20-device FL sim.

The seed trainer ran devices one-by-one — a jitted step per device per local
epoch, retraced for every distinct (partition point, batch shape) pair, with
sequential per-sample-grad estimation at init. The cohort engine fuses each
round (and the whole stats estimation) into one XLA program each.

Both engines run in this process back-to-back on the same scheduler trace
and dataset, so the ratio is robust to machine noise. "Simulation" = stats
estimation + the 10-round training loop (dataset synthesis is identical
common setup for both). Values are emitted in MILLISECONDS, as named.

NOTE the baseline here is conservative: the in-tree sequential engine
already benefits from this PR's shared speedups (vectorized DDSRA partition
search and Hungarian inner loop, jitted FedAvg, cached eval forward), which
the seed did not have. Measured against the untouched seed commit, the same
simulation is >5x slower than the cohort engine on a 2-core CPU box (seed
32.8s vs cohort 5.0s when this bench was written); the emitted speedup vs
the improved in-tree sequential path is the lower bound.

Part two sweeps cohort scale: {20, 64, 128} devices x engine
(single-width cohort, 4-tier cohort, 4-tier sharded cohort), reporting
per-round wall time and the padded-vs-real sample ratio — the tiered slot
layout recovers most of the batch-padding waste of the single-width
contract, and the sharded engine splits the slot axis over the
``"cohort"`` mesh (1 device on the CPU dev box; run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to see an actual
mesh).

Part three (``--churn`` in the harness / ``churn_sweep=True``) sweeps the
fault axes instead of the machine: churn x straggler tail, comparing the
async engine's synchronous-barrier mode against FedBuff-style buffering on
*simulated* round delay and loss progress. Saves
``artifacts/benchmarks/fl_round_bench_churn.json``.

Part five (``--model {vgg,transformer,ssm}`` / ``model="..."``) runs the
cohort round across the model zoo behind the ``SplitModel`` interface:
same topology, same scheduler, different architecture (and for the token
models, the Markov token data plane + flash-attention kernels). Reports
per-round steady-state time and the one-compile contract per model.
Saves ``artifacts/benchmarks/fl_round_bench_model_<name>.json``.

Part four (``--fused`` / ``fused_sweep=True``) benches the fused simulation
loop (``repro.fl.fused_sim``) on the traced data plane
(``Scenario.data_plane="traced"``: batches gathered in-scan from
device-resident shard stacks — zero per-round host transfers): steady-state
rounds/sec of the stepwise ``Simulation.rounds()`` loop vs
``fused_rounds()`` (one decide scan + one train scan) on the 20-device
topology, asserting the fused path holds a >= 3x edge and that a whole run
costs zero retraces once warm; then the sweep farm (``Simulation.sweep()``):
the seeds x V grid and the policies x seeds x V multi-policy grid
(``repro.core.policy_sweep``), asserting each is ONE compiled program
across value changes and recording the one-program grid's wall-clock
against one-program-per-policy sweeps of the same lanes.
Saves ``artifacts/benchmarks/fl_round_bench_fused.json``.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit, save_json, timed
from repro import obs
from repro.core.network import NetworkConfig
from repro.fl import Scenario, Simulation

ROUNDS, DEVICES, GATEWAYS = 10, 20, 5

# (n_devices, n_gateways, n_channels) for the scaling sweep
SCALE_SWEEP = [(20, 5, 3), (64, 8, 4), (128, 16, 8)]
# (engine, tiers) variants: single-width cohort is the historical contract
SCALE_ENGINES = [("cohort", 1), ("cohort", 4), ("sharded", 4)]

# -- churn/straggler sweep (``--churn`` / ``churn_sweep=True``) -------------
# churn rates x straggler tails, each run under both aggregation modes of
# the async engine: the barrier sentinel (buffer_k=None — synchronous
# FedAvg semantics, the server waits for the slowest surviving report) and
# FedBuff-style buffering (aggregate at K landings, stragglers keep flying).
CHURN_RATES = [0.0, 0.1, 0.3]
STRAGGLER_TAILS = [(0.0, 0.0), (0.5, 1.0), (0.5, 3.0)]   # (frac, scale)
CHURN_MODES = [("sync_barrier", None), ("async_buffered", 2)]


def _simulate(engine: str):
    sc = Scenario(model="mlp", rounds=ROUNDS, seed=0, engine=engine,
                  net=NetworkConfig(n_gateways=GATEWAYS, n_devices=DEVICES,
                                    n_channels=3))
    sim = Simulation(sc)                  # init runs estimate_stats (timed)
    with timed() as t_run:
        res = sim.run("ddsra")
    return sim.stats_seconds, t_run["s"], res


def _scale_run(n_dev: int, n_gw: int, n_ch: int, engine: str, tiers: int,
               rounds: int):
    """One sweep point: short ddsra-scheduled sim at the given scale.

    Rounds are timed individually; ``round_ms`` is the mean over the
    steady-state rounds (the first round pays XLA compilation and the last
    pays the accuracy eval, so both are excluded)."""
    sc = Scenario(model="mlp", rounds=rounds, eval_every=rounds + 1, seed=0,
                  engine=engine, tiers=tiers, alpha=0.2, max_dataset=250,
                  net=NetworkConfig(n_gateways=n_gw, n_devices=n_dev,
                                    n_channels=n_ch))
    sim = Simulation(sc)
    per_round, records = [], []
    it = sim.rounds("ddsra")
    for _ in range(rounds):
        with timed() as t:
            records.append(next(it))
        per_round.append(t["s"])
    steady = per_round[1:-1] if rounds > 2 else per_round[-1:]
    real = sim.padding_stats["real_samples"]
    padded = sim.padding_stats["padded_samples"]
    return {
        "devices": n_dev, "engine": engine, "tiers": tiers,
        "rounds": rounds, "stats_s": sim.stats_seconds,
        "run_s": sum(per_round), "compile_round_s": per_round[0],
        "round_ms": sum(steady) * 1e3 / len(steady),
        "real_samples": real, "padded_samples": padded,
        "pad_ratio": padded / max(real, 1.0),
        "final_loss": float(np.mean(records[-1].losses)),
    }


TARGET_LOSS = 0.5        # rounds/delay-to-target threshold (initial ~2.3)


def _churn_run(churn: float, frac: float, scale: float, buffer_k,
               budget_s: float, stats):
    """One sweep point: a faulted async-engine run on the shared topology,
    run until ``budget_s`` of *simulated* time has elapsed (both modes get
    the same wall of simulated seconds — the only fair axis when round
    delays differ by design).

    ``stats`` (precomputed per-device statistics) is threaded into every
    run so no estimation draws are consumed and every point replays the
    identical schedule/batch/fault streams — the sweep isolates the
    aggregation mode."""
    cap = 400               # hard round cap under the time budget
    sc = Scenario(model="mlp", rounds=cap, eval_every=cap + 1, seed=0,
                  alpha=0.2, max_dataset=250, engine="async", churn=churn,
                  straggler_frac=frac, straggler_scale=scale,
                  buffer_k=buffer_k,
                  net=NetworkConfig(n_gateways=GATEWAYS, n_devices=DEVICES,
                                    n_channels=3))
    sim = Simulation(sc, _stats=stats)
    recs = []
    for rec in sim.rounds("ddsra"):
        recs.append(rec)
        if rec.cum_delay >= budget_s:
            break
    mean_loss = [float(np.mean(r.losses)) for r in recs]
    to_target = next((i for i, l in enumerate(mean_loss)
                      if l <= TARGET_LOSS), None)
    n = len(recs)
    return {
        "churn": churn, "straggler_frac": frac, "straggler_scale": scale,
        "mode": "sync_barrier" if buffer_k is None else "async_buffered",
        "buffer_k": buffer_k, "budget_s": budget_s,
        "rounds_in_budget": n,
        "mean_round_delay": recs[-1].cum_delay / n,
        "cum_delay": recs[-1].cum_delay,
        "loss_at_budget": mean_loss[-1],
        "target_loss": TARGET_LOSS,
        "rounds_to_target": None if to_target is None else to_target + 1,
        "delay_to_target": (None if to_target is None
                            else recs[to_target].cum_delay),
        "aggregations": sum(r.aggregations for r in recs),
        "dropped_devices": sum(r.dropped_devices for r in recs),
        "straggler_devices": sum(r.straggler_devices for r in recs),
        "stale_discarded": sum(r.stale_discarded for r in recs),
        "staleness_max": max(r.staleness_max for r in recs),
        "loss_curve": mean_loss,
        "cum_delay_curve": [r.cum_delay for r in recs],
    }


def churn_main(fast: bool = True) -> None:
    """Churn/straggler sweep: sync-barrier vs buffered aggregation.

    The claim under test: as the straggler tail grows, the synchronous
    barrier's mean round delay degrades (it waits for the slowest surviving
    report every round) while buffered aggregation stays near-flat (a late
    update delays itself, not the round) — so at an equal simulated-time
    budget the buffered mode completes more rounds and reaches the target
    loss sooner. Emits one line per sweep point and saves
    ``fl_round_bench_churn.json``.
    """
    budget_s = 30.0 if fast else 90.0
    # per-device stats depend only on the fault-free topology/data; compute
    # once and thread into every point (see _churn_run).
    stats = Simulation(Scenario(
        model="mlp", rounds=1, seed=0, alpha=0.2, max_dataset=250,
        net=NetworkConfig(n_gateways=GATEWAYS, n_devices=DEVICES,
                          n_channels=3))).stats

    points = []
    for churn in CHURN_RATES:
        for frac, scale in STRAGGLER_TAILS:
            for mode, buffer_k in CHURN_MODES:
                pt = _churn_run(churn, frac, scale, buffer_k, budget_s,
                                stats)
                points.append(pt)
                emit(f"fl_churn{churn}_tail{scale}_{mode}_delay_s",
                     pt["mean_round_delay"],   # simulated seconds (see name)
                     f"rounds={pt['rounds_in_budget']};"
                     f"loss_at_budget={pt['loss_at_budget']:.3f};"
                     f"delay_to_target="
                     f"{pt['delay_to_target'] or float('nan'):.1f};"
                     f"stale_max={pt['staleness_max']}")

    def _pt(mode, scale, churn):
        return next(p for p in points
                    if p["churn"] == churn and p["straggler_scale"] == scale
                    and p["mode"] == mode)

    for churn in CHURN_RATES:
        for frac, scale in STRAGGLER_TAILS:
            sync, asyn = (_pt("sync_barrier", scale, churn),
                          _pt("async_buffered", scale, churn))
            print(f"  churn={churn:.1f} tail={scale:.1f}: round delay "
                  f"sync {sync['mean_round_delay']:.2f}s vs async "
                  f"{asyn['mean_round_delay']:.2f}s | loss@{budget_s:.0f}s "
                  f"{sync['loss_at_budget']:.3f} vs "
                  f"{asyn['loss_at_budget']:.3f} | rounds "
                  f"{sync['rounds_in_budget']} vs "
                  f"{asyn['rounds_in_budget']}")

    # the headline claims, asserted so a regression fails the bench. Growth
    # is measured *additively* (seconds of extra delay per round as the
    # tail goes 0 -> 3.0x): the buffered mode's tail-free delay is near
    # zero (the backlog always holds already-landed arrivals), so a ratio
    # would explode off a tiny base even while the absolute delay stays
    # flat — which is the whole point.
    sync_growth = (_pt("sync_barrier", 3.0, 0.0)["mean_round_delay"]
                   - _pt("sync_barrier", 0.0, 0.0)["mean_round_delay"])
    async_growth = (_pt("async_buffered", 3.0, 0.0)["mean_round_delay"]
                    - _pt("async_buffered", 0.0, 0.0)["mean_round_delay"])
    print(f"  straggler tail 0 -> 3.0x: sync delay +{sync_growth:.2f}s per "
          f"round, async +{async_growth:.2f}s")
    assert sync_growth > 2.0 * async_growth, \
        "buffered aggregation no longer absorbs the straggler tail"
    for churn in CHURN_RATES:        # buffering always wins on round delay
        for _, scale in STRAGGLER_TAILS:
            assert (_pt("async_buffered", scale, churn)["mean_round_delay"]
                    < _pt("sync_barrier", scale, churn)["mean_round_delay"])
    assert (_pt("async_buffered", 3.0, 0.3)["loss_at_budget"]
            < _pt("sync_barrier", 3.0, 0.3)["loss_at_budget"]), \
        "buffered aggregation lost its loss-per-simulated-second edge"

    save_json("fl_round_bench_churn", {
        "budget_s": budget_s, "devices": DEVICES, "gateways": GATEWAYS,
        "target_loss": TARGET_LOSS,
        "sync_tail_delay_growth_s": sync_growth,
        "async_tail_delay_growth_s": async_growth,
        "sweep": points,
    })


def fused_main(fast: bool = True) -> None:
    """Fused simulation loop vs the stepwise round loop, plus the sweep farm.

    Both paths run the identical trajectory (the parity matrix in
    ``tests/test_fused_sim.py`` pins them bit-identical on queues/RNG), so
    the rounds/sec ratio isolates the loop structure: per-round dispatch +
    host repackaging vs one decide scan + one train scan. Compile counts
    are asserted in-bench via the ``repro.obs`` trace counters: a warm
    fused run retraces nothing, and the whole seeds x V sweep grid stays
    one executable across value changes.

    Workload: 20 devices (the paper topology's device count) spread over
    10 gateways contending for 2 channels — the channel-scarce regime DDSRA
    targets, and the one where the simulation loop itself (per-round decide
    dispatch, decision repackaging, per-gateway loss syncs) is the cost
    rather than raw training FLOPs. A narrow MLP + one local iteration
    keeps per-round train compute at the few-ms scale of real edge rounds;
    heavier models push both paths into compute-bound territory where the
    loop structure (correctly) stops mattering. Steady-state = best of
    ``REPS`` timed passes after a warm pass.
    """
    rounds = 30 if fast else 60
    reps = 5
    # traced data plane: both paths sample batches with the counter-based
    # jax draws (identical trajectories — the traced parity tests pin
    # them), but only the fused path gets to keep them on device: its
    # batch phase is metadata-only, while stepwise still dispatches
    # per-round programs.
    sc = Scenario(model="mlp", mlp_hidden=(32,), rounds=rounds,
                  eval_every=rounds + 1, seed=0, alpha=0.03, k_iters=1,
                  max_dataset=200, policy="ddsra_jax", data_plane="traced",
                  net=NetworkConfig(n_gateways=10, n_devices=DEVICES,
                                    n_channels=2))
    sim = Simulation(sc)

    # -- warm both paths (compiles), then interleave the timed reps: load
    # on a shared box drifts over seconds, and timing every stepwise pass
    # before every fused pass folds that drift straight into the ratio.
    # Alternating passes exposes both paths to the same conditions;
    # best-of-reps keeps the steady-state floor of each.
    recs = list(sim.rounds())
    assert all(r.trained for r in recs), "degenerate bench: idle rounds"
    sim.reset()
    sim.fused_rounds()     # warm pass traces decide + train scans
    counted = ("trace.ddsra.decide", "trace.ddsra.round",
               "trace.cohort.train_scan", "trace.cohort.round")
    before = {k: obs.counters[k] for k in counted}
    step_s, fused_s = [], []
    for _ in range(reps):
        sim.reset()
        with timed() as t_step:
            list(sim.rounds())
        step_s.append(t_step["s"])
        sim.reset()
        with timed() as t_fused:
            sim.fused_rounds()
        fused_s.append(t_fused["s"])
    step_rps = rounds / min(step_s)
    retraces = int(sum(obs.counters[k] - before[k] for k in counted))
    fused_rps = rounds / min(fused_s)
    speedup = fused_rps / step_rps

    emit("fl_fused_rounds_per_s", fused_rps,
         f"stepwise={step_rps:.2f};speedup={speedup:.2f}x;"
         f"retraces={retraces}")
    print(f"  {rounds}-round/{DEVICES}-device run: stepwise "
          f"{step_rps:.2f} rounds/s vs fused {fused_rps:.2f} rounds/s "
          f"-> {speedup:.2f}x ({retraces} retraces on the warm run)")
    assert retraces == 0, "warm fused run retraced a scan"
    assert speedup >= 3.0, \
        f"fused loop lost its >=3x rounds/sec edge ({speedup:.2f}x)"

    # -- the sweep farm: seeds x V as ONE compiled program -----------------
    seeds, v_values = [0, 1, 2], [0.01, 1.0, 100.0]
    sweep_rounds = rounds
    sim.sweep(v_values, seeds=seeds, rounds=sweep_rounds)        # warm
    before_sweep = obs.counters["trace.ddsra.sweep"]
    with timed() as t_sweep:
        res = sim.sweep([0.05, 5.0, 500.0], seeds=[3, 4, 5],
                        rounds=sweep_rounds)
    sweep_retraces = int(obs.counters["trace.ddsra.sweep"] - before_sweep)
    lanes = len(seeds) * len(v_values)
    lane_rps = lanes * sweep_rounds / t_sweep["s"]
    emit("fl_sweep_lane_rounds_per_s", lane_rps,
         f"lanes={lanes};rounds={sweep_rounds};"
         f"retraces={sweep_retraces}")
    print(f"  sweep farm: {lanes} (seed, V) lanes x {sweep_rounds} rounds "
          f"in {t_sweep['s']:.2f}s ({lane_rps:.1f} lane-rounds/s), "
          f"{sweep_retraces} retraces across value changes")
    assert sweep_retraces == 0, \
        "the seeds x V sweep stopped being one compiled program"
    assert res.taus.shape == (3, 3, sweep_rounds)

    # -- multi-policy grid: policies x seeds x V as ONE program vs one
    # program per policy (the pre-PR-10 shape of the fig456 sweep) --------
    policies = ["ddsra_jax", "round_robin", "random", "delay_driven"]
    sim.sweep(v_values, seeds=seeds, rounds=sweep_rounds,
              policies=policies)                                 # warm
    before_mp = obs.counters["trace.policy_sweep.sweep"]
    with timed() as t_mp:
        res_mp = sim.sweep([0.05, 5.0, 500.0], seeds=[3, 4, 5],
                           rounds=sweep_rounds, policies=policies)
    mp_retraces = int(obs.counters["trace.policy_sweep.sweep"] - before_mp)
    assert mp_retraces == 0, \
        "the multi-policy sweep stopped being one compiled program"
    assert res_mp.taus.shape == (len(policies), 3, 3, sweep_rounds)
    # per-policy baseline: same lanes as P single-policy programs (warm
    # each shape first so the comparison is wall-clock, not compile time)
    for p in policies:
        sim.sweep(v_values, seeds=seeds, rounds=sweep_rounds, policies=[p])
    with timed() as t_pp:
        for p in policies:
            sim.sweep([0.05, 5.0, 500.0], seeds=[3, 4, 5],
                      rounds=sweep_rounds, policies=[p])
    mp_speedup = t_pp["s"] / t_mp["s"]
    emit("fl_multi_policy_sweep_s", t_mp["s"],
         f"policies={len(policies)};per_policy_s={t_pp['s']:.2f};"
         f"speedup={mp_speedup:.2f}x;retraces={mp_retraces}")
    print(f"  multi-policy grid: {len(policies)} policies x {lanes} lanes "
          f"x {sweep_rounds} rounds in {t_mp['s']:.2f}s as ONE program vs "
          f"{t_pp['s']:.2f}s as per-policy programs ({mp_speedup:.2f}x)")

    save_json("fl_round_bench_fused", {
        "rounds": rounds, "devices": DEVICES,
        "gateways": sc.net.n_gateways, "channels": sc.net.n_channels,
        "data_plane": sc.data_plane,
        "stepwise_rounds_per_s": step_rps,
        "fused_rounds_per_s": fused_rps,
        "fused_speedup": speedup,
        "fused_retraces_warm": retraces,
        "sweep_lanes": lanes, "sweep_rounds": sweep_rounds,
        "sweep_s": t_sweep["s"],
        "sweep_lane_rounds_per_s": lane_rps,
        "sweep_retraces_across_value_changes": sweep_retraces,
        "multi_policy_policies": policies,
        "multi_policy_sweep_s": t_mp["s"],
        "per_policy_sweeps_s": t_pp["s"],
        "multi_policy_speedup": mp_speedup,
        "multi_policy_retraces": mp_retraces,
    })


# model-zoo bench points: one Scenario tweak per SplitModel family
MODEL_SCENARIOS = {
    "vgg": {"model": "vgg", "width_mult": 0.1},
    "transformer": {"model": "transformer", "seq_len": 16},
    "ssm": {"model": "ssm", "seq_len": 16},
}


def model_main(model: str, fast: bool = True) -> None:
    """Cohort round time for one model-zoo member (``--model NAME``)."""
    if model not in MODEL_SCENARIOS:
        raise SystemExit(
            f"unknown --model {model!r}; choose from {sorted(MODEL_SCENARIOS)}")
    rounds = 4 if fast else 10
    sc = Scenario(rounds=rounds, eval_every=rounds + 1, seed=0, alpha=0.2,
                  max_dataset=250, engine="cohort",
                  net=NetworkConfig(n_gateways=4, n_devices=12, n_channels=2),
                  **MODEL_SCENARIOS[model])
    sim = Simulation(sc)
    traces_before = int(obs.counters["trace.cohort.round"])
    per_round, records = [], []
    it = sim.rounds("ddsra")
    for _ in range(rounds):
        with timed() as t:
            records.append(next(it))
        per_round.append(t["s"])
    traces = int(obs.counters["trace.cohort.round"]) - traces_before
    steady = per_round[1:] if rounds > 1 else per_round
    round_ms = sum(steady) * 1e3 / len(steady)
    emit(f"fl_model_{model}_round_ms", round_ms,
         f"blocks={sim.plan.n_blocks};cuts={len(sim.plan.valid_cuts)};"
         f"compile_s={per_round[0]:.1f};compiles={traces}")
    assert traces <= 1, f"{model} cohort step retraced across rounds"
    final_loss = float(np.mean(records[-1].losses))
    assert np.isfinite(final_loss), f"{model} training diverged"
    save_json(f"fl_round_bench_model_{model}", {
        "model": model, "rounds": rounds,
        "devices": sc.net.n_devices, "gateways": sc.net.n_gateways,
        "n_blocks": sim.plan.n_blocks,
        "valid_cuts": len(sim.plan.valid_cuts),
        "stats_s": sim.stats_seconds, "compile_round_s": per_round[0],
        "round_ms": round_ms, "compiles": traces,
        "final_loss": final_loss,
    })


def main(fast: bool = True, churn_sweep: bool = False,
         fused_sweep: bool = False, model: str | None = None) -> None:
    import jax
    jax.numpy.zeros(1).block_until_ready()   # generic runtime warmup

    if model is not None:
        model_main(model, fast=fast)
        return
    if churn_sweep:
        churn_main(fast=fast)
        return
    if fused_sweep:
        fused_main(fast=fast)
        return

    seq_stats_s, seq_run_s, seq_res = _simulate("sequential")

    traces_before = int(obs.counters["trace.cohort.round"])
    co_stats_s, co_run_s, co_res = _simulate("cohort")
    traces = int(obs.counters["trace.cohort.round"]) - traces_before

    speedup = (seq_stats_s + seq_run_s) / (co_stats_s + co_run_s)
    run_speedup = seq_run_s / co_run_s
    stats_speedup = seq_stats_s / co_stats_s

    emit("fl_round_ms", co_run_s * 1e3 / ROUNDS,
         f"seq_ms={seq_run_s * 1e3 / ROUNDS:.1f};speedup={run_speedup:.1f}x;"
         f"cohort_compiles={traces}")
    emit("estimate_stats_ms", co_stats_s * 1e3,
         f"seq_ms={seq_stats_s * 1e3:.1f};speedup={stats_speedup:.1f}x")
    print(f"  {ROUNDS}-round/{DEVICES}-device simulation (stats + training):"
          f" cohort {co_stats_s + co_run_s:.2f}s vs sequential"
          f" {seq_stats_s + seq_run_s:.2f}s -> {speedup:.1f}x,"
          f" {traces} cohort-step compile(s)")
    assert traces <= 1, "cohort step retraced across rounds"
    # both engines must tell the same training story (parity is pinned
    # tightly in tests/test_cohort.py; this guards the bench itself)
    assert abs(seq_res.accuracy[-1] - co_res.accuracy[-1]) < 0.05

    # -- scaling sweep: {20, 64, 128} devices x engine x slot layout -------
    n_mesh = len(jax.devices())
    sweep = []
    for n_dev, n_gw, n_ch in SCALE_SWEEP:
        rounds = (5 if n_dev <= 20 else 4) if fast else 10
        for engine, tiers in SCALE_ENGINES:
            rec = _scale_run(n_dev, n_gw, n_ch, engine, tiers, rounds)
            sweep.append(rec)
            emit(f"fl_scale_{n_dev}dev_{engine}_t{tiers}_round_ms",
                 rec["round_ms"],
                 f"pad_ratio={rec['pad_ratio']:.2f};"
                 f"compile_s={rec['compile_round_s']:.1f};"
                 f"mesh={n_mesh}")
        flat = next(r for r in sweep if r["devices"] == n_dev
                    and r["engine"] == "cohort" and r["tiers"] == 1)
        tier = next(r for r in sweep if r["devices"] == n_dev
                    and r["engine"] == "cohort" and r["tiers"] == 4)
        saved = 1.0 - tier["padded_samples"] / flat["padded_samples"]
        print(f"  {n_dev:3d} devices: tiered slots drop padded samples "
              f"{flat['padded_samples']:.0f} -> {tier['padded_samples']:.0f} "
              f"(-{saved:.0%}); pad ratio {flat['pad_ratio']:.2f} -> "
              f"{tier['pad_ratio']:.2f}")
        assert tier["padded_samples"] <= flat["padded_samples"], \
            "tiered layout must not pad more than the single-width contract"

    save_json("fl_round_bench", {
        "rounds": ROUNDS, "devices": DEVICES,
        "cohort_stats_s": co_stats_s, "cohort_run_s": co_run_s,
        "sequential_stats_s": seq_stats_s, "sequential_run_s": seq_run_s,
        "speedup": speedup, "run_speedup": run_speedup,
        "stats_speedup": stats_speedup, "cohort_compiles": traces,
        "cohort_mesh_devices": n_mesh,
        "scale_sweep": sweep,
    })


if __name__ == "__main__":
    main()
