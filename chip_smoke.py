"""Smoke run of the main path on a TPU: fused DDSRA scheduling + full-width
VGG-11 split training.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the sharded engine on a 4-chip mesh

One chip: the paper's deployment (6 gateways, 12 devices, 3 channels,
CIFAR-shaped non-IID data, VGG-11 at ``width_mult=1.0``; per-round energy
budgets scaled to the full-width model, see :func:`scenario`) runs through
``Simulation.run_fused("ddsra_jax")`` on the cohort engine with the traced
data plane. The script checks that the losses and accuracy are finite, that
every round trains, that the fused run compiles exactly one decide and one
train program and never retraces, that the train program holds the Pallas
kernels (``tpu_custom_call``), and that a stepwise run of the same scenario
agrees with the fused run's first rounds.

``--four-chips``: the same scenario on ``engine="sharded"`` over a 4-chip
``"cohort"`` mesh, checked against the cohort engine on chip 0 in the same
process, and nothing else.

The lines before the last are smoke numbers, not benchmark results. The last
line is one JSON object ``{"ok": true, "device": {...}}``. The script exits
non-zero and prints no such line when JAX finds no TPU, when a
``REPRO_*_IMPL`` override asks for anything but ``pallas``, or when any
check fails.
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

IMPL_VARS = ("REPRO_FUSED_LINEAR_IMPL", "REPRO_FLASH_ATTENTION_IMPL",
             "REPRO_SSD_SCAN_IMPL")
ROUNDS = 4
STEPWISE_ROUNDS = 2
ENERGY_SCALE = 16.0
# Stepwise and fused runs compile the same round body into two programs. On
# the TPU, f32 convolutions and matmuls run at default precision (bf16
# passes, f32 accumulation) in both, so they differ only in accumulation
# order (~1e-6 relative per op). Five local SGD steps per round for two
# rounds amplify that to ~1e-4 at most; 1e-3 leaves a 10x margin while a
# wrong batch, cut or weight moves a loss by whole percents.
STEPWISE_LOSS_RTOL = 1e-3
# The queues come from two float64 programs, the per-round solve and the
# decide scan; the TPU emulates float64, so they may part in the last bits
# (the queues are O(1); a different pick moves one by 0.1 or more).
QUEUE_ATOL = 1e-12
# Sharded vs cohort: the same per-slot programs on other chips, FedAvg as
# psums over the mesh instead of one tensordot, so reduction order only; but
# the orders differ in every round, and a rehearsal on a 4-device CPU mesh
# already shows losses 1.6e-5 apart and params 0.4% of their 4-round update
# apart. The bounds leave margin for the TPU's bf16 passes while a wrong
# FedAvg weight or a slot trained twice moves both by tens of percent.
MESH_LOSS_RTOL = 1e-2
MESH_PARAM_RTOL = 5e-2      # max |a - b| over max |a - a_init|, per leaf
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond, msg: str) -> None:
    if not cond:
        fail(msg)


def say(key: str, value) -> None:
    print(f"smoke {key}={value}", flush=True)


def scenario(**kw):
    from repro.core.network import NetworkConfig
    from repro.fl import Scenario
    # The default network (6 gateways, 12 devices, 3 channels) with its
    # per-round energy budgets scaled by 16: training energy grows with the
    # FLOPs per sample, and full-width VGG-11 has 15.5x those of the
    # Scenario's default width 0.25. At the default 5 J / 30 J, DDSRA finds
    # no feasible gateway for full-width VGG-11 and no device trains.
    d = NetworkConfig()
    net = NetworkConfig(e_dev_max=ENERGY_SCALE * d.e_dev_max,
                        e_gw_max=ENERGY_SCALE * d.e_gw_max)
    base = dict(model="vgg", width_mult=1.0, engine="cohort",
                data_plane="traced", rounds=ROUNDS, eval_every=ROUNDS,
                seed=0, net=net)
    base.update(kw)
    return Scenario(**base)


def max_rel_diff(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(a), 1e-12)))


def one_chip(jax, compile_s: dict) -> None:
    from repro import obs
    from repro.fl import Simulation
    from repro.fl import cohort as cohort_lib

    # keep the train program's arguments, to read its compiled HLO after
    # the runs; the call itself goes through unchanged
    train_call = {}
    train_scan = cohort_lib.train_scan_traced

    def recording_train_scan(*a, **k):
        train_call.setdefault("args", (a, k))
        return train_scan(*a, **k)

    cohort_lib.train_scan_traced = recording_train_scan

    def traces():
        return (obs.counters["trace.ddsra.decide"],
                obs.counters["trace.cohort.train_scan"])

    t0 = time.perf_counter()
    sim = Simulation(scenario())
    say("setup_s", time.perf_counter() - t0)
    n_params = sum(int(x.size) for x in jax.tree.leaves(sim.params))
    say("vgg11_params", n_params)

    # -- the fused run: first call compiles the decide and train programs
    before = traces()
    t0 = time.perf_counter()
    res = sim.run_fused("ddsra_jax")
    say("first_fused_run_s", time.perf_counter() - t0)
    first = tuple(b - a for a, b in zip(before, traces()))
    check(first == (1, 1),
          f"fused run traced (decide, train) {first} times, expected (1, 1)")
    check(len(res.losses) == ROUNDS and np.all(np.isfinite(res.losses)),
          f"non-finite fused losses {res.losses}")
    check(res.accuracy and np.all(np.isfinite(res.accuracy)),
          f"no finite final accuracy: {res.accuracy}")
    say("losses", [float(x) for x in res.losses])
    say("final_accuracy", float(res.accuracy[-1]))
    decide_s = sum(v for k, v in compile_s.items() if "decide_scan" in k)
    train_s = sum(v for k, v in compile_s.items() if "train_scan" in k)
    say("compile_s_decide_program", decide_s)
    say("compile_s_train_program", train_s)

    # -- steady state: same trajectory again, nothing may retrace
    sim.reset()
    before = traces()
    t0 = time.perf_counter()
    records = sim.fused_rounds("ddsra_jax")
    jax.block_until_ready(sim.params)
    steady_s = time.perf_counter() - t0
    again = tuple(b - a for a, b in zip(before, traces()))
    check(again == (0, 0), f"warm fused run retraced {again}")
    say("steady_rounds_per_s", ROUNDS / steady_s)
    check(np.array_equal(np.asarray([r.selected for r in records]),
                         res.participation),
          "a replay of the fused run picked other gateways")
    trained = [sum(len(sim.gateways[m].devices) for m in r.trained)
               for r in records]
    say("devices_trained_per_round", trained)
    check(min(trained) > 0, f"a round trained no device: {trained}")
    for r in records:
        check(np.all(np.isfinite(r.losses)),
              f"round {r.t}: non-finite losses {r.losses}")

    # -- stepwise rounds of the same scenario against the fused records
    sim.reset()
    stepwise = list(itertools.islice(sim.rounds("ddsra_jax"),
                                     STEPWISE_ROUNDS))
    worst = q_diff = 0.0
    for s, f in zip(stepwise, records):
        check(np.array_equal(s.selected, f.selected)
              and s.trained == f.trained
              and np.array_equal(s.l_n, f.l_n),
              f"round {s.t}: stepwise picked other devices or cuts")
        q_diff = max(q_diff, float(np.max(np.abs(s.queues - f.queues))))
        check(q_diff <= QUEUE_ATOL, f"round {s.t}: Lyapunov queues differ "
              f"{s.queues} vs {f.queues}")
        worst = max(worst, max_rel_diff(f.losses, s.losses))
    say("stepwise_vs_fused_queue_max_abs_diff", q_diff)
    say("stepwise_vs_fused_loss_max_rel_diff", worst)
    say("stepwise_vs_fused_loss_rtol", STEPWISE_LOSS_RTOL)
    check(worst <= STEPWISE_LOSS_RTOL,
          f"stepwise losses differ from fused by {worst}")

    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    say("peak_bytes_in_use", peak)

    # -- the train program runs the Pallas fc kernels, not fused_linear_ref
    a, k = train_call["args"]
    hlo = train_scan.lower(*a, **k).compile().as_text()
    n_kernels = hlo.count('custom_call_target="tpu_custom_call"')
    say("train_program_tpu_custom_calls", n_kernels)
    check(n_kernels > 0, "the train program holds no tpu_custom_call: the fc "
          "layers fell back to the jnp reference")


def four_chips(jax) -> None:
    from repro.fl import Simulation

    devices = jax.devices()
    check(len(devices) == 4, f"--four-chips needs 4 TPU devices, "
          f"found {len(devices)}")
    ref = Simulation(scenario(engine="cohort"))
    # the sharded run reuses the cohort run's data statistics and batch-RNG
    # state, so both face identical participation targets and draws
    shd = Simulation(scenario(engine="sharded", mesh_shape=(4,)),
                     _stats=ref.stats)
    shd.rng.bit_generator.state = ref._rng_state0
    mesh = shd.engine._mesh(shd)
    check(mesh.devices.size == 4, f"sharded mesh holds {mesh.devices.size} "
          "devices")

    init = ref.params
    t0 = time.perf_counter()
    r_ref = ref.run_fused("ddsra_jax")
    say("cohort_chip0_fused_run_s", time.perf_counter() - t0)
    t0 = time.perf_counter()
    r_shd = shd.run_fused("ddsra_jax")
    say("sharded_fused_run_s", time.perf_counter() - t0)

    held = {len(x.sharding.device_set) for x in jax.tree.leaves(shd.params)}
    check(held == {4}, f"sharded params live on {held} devices, expected 4")
    check(np.array_equal(r_ref.participation, r_shd.participation),
          "sharded and cohort runs picked other gateways")
    check(np.array_equal(ref.queues, shd.queues),
          f"Lyapunov queues differ {ref.queues} vs {shd.queues}")
    check(np.all(np.isfinite(r_shd.losses)), f"non-finite sharded losses "
          f"{r_shd.losses}")
    loss_diff = max_rel_diff(r_ref.losses, r_shd.losses)
    say("sharded_vs_cohort_loss_max_rel_diff", loss_diff)
    say("sharded_vs_cohort_loss_rtol", MESH_LOSS_RTOL)
    check(loss_diff <= MESH_LOSS_RTOL,
          f"sharded losses differ from cohort by {loss_diff}")
    param_diff = max(
        float(np.max(np.abs(np.asarray(a) - np.asarray(b)))
              / max(float(np.max(np.abs(np.asarray(a) - np.asarray(a0)))),
                    1e-12))
        for a, b, a0 in zip(jax.tree.leaves(ref.params),
                            jax.tree.leaves(shd.params),
                            jax.tree.leaves(init)))
    say("sharded_vs_cohort_param_max_rel_diff", param_diff)
    say("sharded_vs_cohort_param_rtol", MESH_PARAM_RTOL)
    check(param_diff <= MESH_PARAM_RTOL,
          f"sharded params differ from cohort by {param_diff}")
    say("final_accuracy_cohort", float(r_ref.accuracy[-1]))
    say("final_accuracy_sharded", float(r_shd.accuracy[-1]))
    say("peak_bytes_in_use_per_chip",
        [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices])


def main() -> None:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded engine on a 4-chip mesh "
                         "against the cohort engine on chip 0")
    args = ap.parse_args()

    for var in IMPL_VARS:
        if os.environ.get(var, "pallas") != "pallas":
            fail(f"{var}={os.environ[var]!r}: the smoke run needs the "
                 "compiled Pallas kernels; unset it")
    src = Path(__file__).resolve().parent / "src"
    check((src / "repro").is_dir(), f"no package at {src / 'repro'}")
    sys.path.insert(0, str(src))
    from repro.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()

    import jax
    devices = jax.devices()
    dev = devices[0]
    check(dev.platform == "tpu",
          f"JAX found no TPU (platform {dev.platform!r})")

    compile_s: dict = {}

    def on_event(event, duration, **kw):
        if event == COMPILE_EVENT:
            name = str(kw.get("fun_name", "?"))
            compile_s[name] = compile_s.get(name, 0.0) + duration

    cache_hits = []

    def on_cache_hit(event, **kw):
        if event == CACHE_HIT_EVENT:
            cache_hits.append(1)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    jax.monitoring.register_event_listener(on_cache_hit)
    print(f"# chip smoke run, not a benchmark: {dev.device_kind} "
          f"x{len(devices)}, compile cache {cache_dir}", flush=True)
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(jax)
    else:
        one_chip(jax, compile_s)
    say("compile_s_total", sum(compile_s.values()))
    # compile seconds include loads from the persistent cache: a warm cache
    # makes them small
    say("persistent_cache_hits", len(cache_hits))
    say("wall_s", time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
