"""The one general load generator: it reads a traffic mix
(``bench/traffic/<mix>.json``) and a configuration
(``bench/configs/<config>.json``) and drives the program's public entry
points with them.

A mix names the call it repeats (``"call"``) and that call's parameters:

* ``"fused_rounds"`` — ``Simulation.fused_rounds(policy,
  rounds=rounds_per_call)`` back to back; when the horizon (``horizon``
  rounds) is reached, ``Simulation.reset(seed)``, as a user's repeated
  runs do.
  The unit is one simulated round. The first ``check_calls`` calls run in
  set-up (the first compiles) and are what the comparison checks.
* ``"sweep"`` — ``Simulation.sweep(v_values, seeds=[seed + o for o in
  seed_offsets], rounds=rounds, policies=policies)`` back to back. The unit
  is one lane-round (policies x seeds x V x rounds). The set-up call
  compiles and is what the comparison checks; every window call must
  return it again.

The program gets only the scenario, built with the configuration's
``deployment_seed`` (which fixes the topology and the devices' data, so
every run does the same work at the same shapes), and the run seed through
``Simulation.reset(seed)`` or the sweep's seed list; it makes its weights,
channel draws and batch draws from them itself.
"""
from __future__ import annotations

import contextlib
import hashlib
import sys
import time
import traceback
from typing import Dict, List, Optional

import numpy as np


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def make_scenario(config: dict, traffic: dict, seed: int, **override):
    from repro.core.network import NetworkConfig
    from repro.fl import Scenario
    kw = dict(config["scenario"])
    kw.update(traffic.get("scenario", {}))
    kw.update(override)
    if config.get("mesh_shape") is not None:
        kw["mesh_shape"] = tuple(config["mesh_shape"])
    return Scenario(net=NetworkConfig(**config["net"]), seed=int(seed), **kw)


def deployment_seed(config: dict) -> int:
    """The seed that fixes the configuration's deployment (topology,
    devices' dataset sizes and data); a run's own seed re-seeds everything
    else through ``Simulation.reset(seed)``."""
    return int(config["deployment_seed"])


class FusedRounds:
    unit = "rounds"

    def __init__(self, config: dict, traffic: dict, seed: int,
                 scenario_override: Optional[dict] = None):
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.rpc = int(traffic["rounds_per_call"])
        self.policy = traffic["policy"]
        self.scenario = make_scenario(
            config, traffic, deployment_seed(config),
            rounds=int(traffic["horizon"]),
            eval_every=int(traffic["eval_every"]), v=float(traffic["v"]),
            policy=self.policy, **(scenario_override or {}))
        self.sim = None
        self.kept: Dict = {}
        self.real = self.padded = 0.0
        self.evals = self.trained_rounds = 0

    def setup(self) -> None:
        import jax
        from repro.fl import Simulation
        t0 = time.perf_counter()
        self.sim = sim = Simulation(self.scenario)
        sim.reset(self.seed)
        self.stats_seconds = float(getattr(sim, "stats_seconds", np.nan))
        self.setup_parts = {"simulation_s": time.perf_counter() - t0}
        self.n_test = int(np.size(np.asarray(sim.ds.y_test)))
        params = [jax.device_get(sim.params)]
        records = []
        for i in range(int(self.traffic["check_calls"])):
            t0 = time.perf_counter()
            recs = sim.fused_rounds(self.policy, rounds=self.rpc)
            self.setup_parts[f"call{i}_s"] = time.perf_counter() - t0
            params.append(jax.device_get(sim.params))
            records += [{"selected": np.asarray(r.selected, bool),
                         "trained": list(r.trained),
                         "l_n": np.asarray(r.l_n), "delay": float(r.delay),
                         "queues": np.asarray(r.queues, np.float64),
                         "losses": np.asarray(r.losses, np.float64),
                         "accuracy": r.accuracy} for r in recs]
        self.kept = {"gamma": np.asarray(sim.gamma, np.float64),
                     "stats": {k: np.asarray(getattr(sim.stats, k))
                               for k in ("sigma", "delta", "lipschitz")},
                     "records": records, "params": params}

    def call(self) -> tuple:
        """One block; returns (units done, units failed)."""
        sim = self.sim
        if sim.t >= self.scenario.rounds:
            with _annotate("bench.reset"):
                sim.reset(self.seed)
        before = dict(sim.padding_stats)
        try:
            with _annotate("bench.call"):
                recs = sim.fused_rounds(self.policy, rounds=self.rpc)
        except Exception:                                   # noqa: BLE001
            traceback.print_exc(file=sys.stderr)
            return self.rpc, self.rpc
        after = sim.padding_stats
        self.real += after["real_samples"] - before["real_samples"]
        self.padded += after["padded_samples"] - before["padded_samples"]
        self.evals += sum(r.accuracy is not None for r in recs)
        self.trained_rounds += sum(bool(r.trained) for r in recs)
        ok = all(np.all(np.isfinite(r.losses)) for r in recs) \
            and len(recs) == self.rpc
        return len(recs), 0 if ok else len(recs)

    def counts(self) -> dict:
        return {"real_samples": self.real, "padded_samples": self.padded,
                "trained_rounds": self.trained_rounds,
                "slots": self.sim.cohort_capacity if self.sim else None,
                "evals": self.evals, "eval_rows": self.evals * self.n_test,
                "k_iters": self.scenario.k_iters,
                "width_mult": self.scenario.width_mult,
                "classes": self.scenario.classes}

    def free(self) -> None:
        self.sim = None

    def numbers(self, **kw) -> Dict[str, float]:
        from bench import compare
        return compare.fused_numbers(self.config, self.traffic, self.seed,
                                     self.kept, **kw)


def _digest(out) -> str:
    h = hashlib.sha256()
    for a in (out.taus, out.selected, out.queues):
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


class Sweep:
    unit = "lane-rounds"

    def __init__(self, config: dict, traffic: dict, seed: int,
                 scenario_override: Optional[dict] = None):
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.scenario = make_scenario(config, traffic,
                                      deployment_seed(config),
                                      policy="ddsra_jax",
                                      **(scenario_override or {}))
        self.seeds = [self.seed + int(o) for o in traffic["seed_offsets"]]
        self.v_values = [float(v) for v in traffic["v_values"]]
        self.rounds = int(traffic["rounds"])
        self.policies = list(traffic["policies"])
        self.per_call = (len(self.policies) * len(self.seeds)
                         * len(self.v_values) * self.rounds)
        self.repeat_mismatch = 0

    def _sweep(self):
        return self.sim.sweep(self.v_values, seeds=self.seeds,
                              rounds=self.rounds, policies=self.policies)

    def setup(self) -> None:
        from repro.fl import Simulation
        t0 = time.perf_counter()
        self.sim = sim = Simulation(self.scenario)
        self.stats_seconds = float(getattr(sim, "stats_seconds", np.nan))
        self.setup_parts = {"simulation_s": time.perf_counter() - t0}
        t0 = time.perf_counter()
        out = self._sweep()
        self.setup_parts["call0_s"] = time.perf_counter() - t0
        self.first = _digest(out)
        self.kept = {"gamma": np.asarray(sim.gamma, np.float64),
                     "taus": np.asarray(out.taus),
                     "selected": np.asarray(out.selected),
                     "queues": np.asarray(out.queues)}

    def call(self) -> tuple:
        try:
            with _annotate("bench.call"):
                out = self._sweep()
        except Exception:                                   # noqa: BLE001
            traceback.print_exc(file=sys.stderr)
            return self.per_call, self.per_call
        if _digest(out) != self.first:
            self.repeat_mismatch += 1
            return self.per_call, self.per_call
        if not np.all(np.isfinite(out.taus)):
            return self.per_call, self.per_call
        return self.per_call, 0

    def counts(self) -> dict:
        return {}

    def free(self) -> None:
        self.sim = None

    def numbers(self, **kw) -> Dict[str, float]:
        from bench import compare
        kept = dict(self.kept, repeat_mismatch=self.repeat_mismatch)
        return compare.grid_numbers(self.config, self.traffic, self.seed,
                                    kept, **kw)


DRIVERS = {"fused_rounds": FusedRounds, "sweep": Sweep}


def make(config: dict, traffic: dict, seed: int, **kw):
    call = traffic["call"]
    if call not in DRIVERS:
        raise ValueError(f"traffic call {call!r}: expected one of "
                         f"{sorted(DRIVERS)}")
    return DRIVERS[call](config, traffic, seed, **kw)


def window(gen, seconds: float, on_call=None) -> dict:
    """Call the generator back to back for ``seconds``: every call that starts
    inside the window runs to its end, and the window ends with the last.
    Returns the units done and failed, the window's length and each call's
    latency."""
    lat: List[float] = []
    done = failed = 0
    t0 = time.perf_counter()
    end = t0 + seconds
    while True:
        c0 = time.perf_counter()
        if c0 >= end and lat:
            break
        d, f = gen.call()
        lat.append(time.perf_counter() - c0)
        done += d
        failed += f
        if on_call is not None:
            on_call()
    return {"units": done, "failed": failed,
            "window_s": time.perf_counter() - t0, "latencies_s": lat}


@contextlib.contextmanager
def traced(log_dir: Optional[str]):
    """The profiler on around the block when ``log_dir`` is given."""
    if log_dir is None:
        yield
        return
    import jax
    opts = jax.profiler.ProfileOptions()
    # no Python function events: they are most of a trace's size, slow its
    # reading and load the host; the benchmark's TraceAnnotation spans stay
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
