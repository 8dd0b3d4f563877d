"""repro.obs: host spans, compile attribution and counters, and the spans
of one fused block in a CPU profiler trace."""
import collections
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.core.network import NetworkConfig
from repro.fl.sim import Scenario, Simulation


def _xplane(log_dir):
    found = sorted(log_dir.glob("plugins/profile/*/*.xplane.pb"))
    assert found, "the profiler wrote no trace"
    return ProfileData.from_file(str(found[-1]))


def _host_spans(data, prefixes=("repro.", "bench.")):
    """(name, start_ns, end_ns, stats) of the host events with a prefix."""
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefixes):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def test_span_nesting_self_time_and_totals():
    before = dict(obs.totals)
    with obs.span("repro.t1") as root:
        with obs.span("repro.t1.a") as a:
            with obs.span("repro.t1.a.x"):
                pass
        with obs.span("repro.t1.a"):
            pass
        with obs.span("repro.t1.b") as b:
            assert obs.current() == "repro.t1.b"
        assert obs.current() == "repro.t1"
    assert obs.current() == ""
    assert root.seconds >= a.seconds + b.seconds > 0
    # every span inside the outermost one, summed by name
    assert set(root.parts) == {"repro.t1.a", "repro.t1.a.x", "repro.t1.b"}
    assert root.parts["repro.t1.a"] >= a.seconds
    assert root.parts["repro.t1.a.x"] <= a.seconds
    assert obs.calls[-1] is root and a not in obs.calls
    n0 = before.get("repro.t1.a", [0, 0.0])[0]
    assert obs.totals["repro.t1.a"][0] == n0 + 2
    assert root.seq == obs.totals["repro.t1"][0]


def test_span_closes_on_error():
    with pytest.raises(RuntimeError):
        with obs.span("repro.t2"):
            raise RuntimeError("boom")
    assert obs.current() == ""
    assert obs.calls[-1].name == "repro.t2"


def test_counters():
    n0 = obs.counters.get("trace.test.thing", 0)
    obs.count("trace.test.thing")
    obs.count("trace.test.thing", 2)
    assert obs.counters["trace.test.thing"] == n0 + 3


def test_compile_attributed_to_innermost_span():
    inner = jax.jit(lambda x: jnp.cos(x) * 2.0)

    @jax.jit
    def f(x):
        return inner(x) + jnp.sin(x)

    x3, x5 = jnp.ones(3), jnp.ones(5)
    f(x3).block_until_ready()
    key = "compile.repro.t3.step"
    n0 = obs.counters.get(f"{key}.n", 0)
    t0 = {k: obs.counters.get(f"{key}.{k}", 0.0)
          for k in ("trace_s", "lower_s", "backend_s")}
    outer0 = obs.counters.get("compile.repro.t3.n", 0)
    with obs.span("repro.t3"):
        with obs.span("repro.t3.step"):
            f(x3).block_until_ready()                 # cached: nothing
            assert obs.counters.get(f"{key}.n", 0) == n0
            f(x5).block_until_ready()                 # a new shape
    # one program traced at the top (the nested jit folded into it)
    assert obs.counters[f"{key}.n"] == n0 + 1
    for k, v in t0.items():
        assert obs.counters[f"{key}.{k}"] > v, k
    assert obs.counters.get("compile.repro.t3.n", 0) == outer0


def test_span_attributes_reach_the_trace(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("repro.t4", block=3, rounds=4):
            with obs.span("repro.t4.step", block=3):
                pass
    spans = {n: st for n, _, _, st in _host_spans(_xplane(tmp_path))}
    assert spans["repro.t4"] == {"block": 3, "rounds": 4}
    assert spans["repro.t4.step"] == {"block": 3}


def test_fused_block_spans_nest_inside_the_call(tmp_path):
    sc = Scenario(model="mlp", alpha=0.2, max_dataset=120, rounds=8,
                  k_iters=2, eval_every=4, net=NetworkConfig(3, 9, 2),
                  policy="ddsra_jax", data_plane="traced")
    sim = Simulation(sc)
    sim.fused_rounds(rounds=4)                    # compiles
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.call"):
            sim.fused_rounds(rounds=4)
    spans = _host_spans(_xplane(tmp_path))
    (call,) = [sp for sp in spans if sp[0] == "bench.call"]
    (root,) = [sp for sp in spans if sp[0] == "repro.fused"]
    steps = [sp for sp in spans if sp[0].startswith("repro.fused.")]
    assert {sp[0] for sp in steps} == {
        "repro.fused.draw", "repro.fused.decide.plan",
        "repro.fused.decide.dispatch",
        "repro.fused.decide.wait", "repro.fused.decide.fetch",
        "repro.fused.pack",
        "repro.fused.train.dispatch", "repro.fused.train.wait",
        "repro.fused.records"}
    assert call[1] <= root[1] <= root[2] <= call[2]
    for name, s, e, _ in steps:
        assert root[1] <= s <= e <= root[2], name
    assert root[3] == {"block": 4, "rounds": 4}
    assert {sp[3]["block"] for sp in steps} == {4}
    # the steps follow one another in the block's order
    order = [sp[0] for sp in sorted(steps, key=lambda sp: sp[1])]
    assert order[0] == "repro.fused.draw"
    assert order[-1] == "repro.fused.records"
    assert order.index("repro.fused.decide.wait") \
        < order.index("repro.fused.pack") \
        < order.index("repro.fused.train.dispatch")
    # the in-memory record of the same call splits it the same way
    rec = [c for c in obs.calls if c.name == "repro.fused"][-1]
    assert rec.attrs == {"block": 4, "rounds": 4}
    assert set(rec.parts) == {sp[0] for sp in steps}
    assert rec.seconds >= sum(rec.parts.values()) * (1 - 1e-9)


def test_setup_spans_and_stats_seconds():
    n0 = {k: obs.totals.get(k, [0, 0.0])[0]
          for k in ("repro.setup.data", "repro.setup.weights",
                    "repro.setup.stats", "repro.reset")}
    sim = Simulation(Scenario(model="mlp", alpha=0.2, max_dataset=120,
                              rounds=2, net=NetworkConfig(3, 9, 2)))
    sim.reset(7)
    for k, n in n0.items():
        assert obs.totals[k][0] == n + 1, k
    stats = [c for c in obs.calls if c.name == "repro.setup.stats"][-1]
    assert sim.stats_seconds == stats.seconds > 0
    assert np.isfinite(sim.stats_seconds)


SCOPES = ("gather", "local_sgd", "fedavg", "eval")


def _op_kinds(hlo_text: str) -> collections.Counter:
    """HLO instructions by opcode over the whole optimized module."""
    kinds = collections.Counter()
    for line in hlo_text.splitlines():
        _, eq, rest = line.partition(" = ")
        m = re.search(r"(?<![\w.\-])([a-z][\w\-]*)\(", rest) if eq else None
        if m:
            kinds[m.group(1)] += 1
    return kinds


def _train_program(engine: str):
    """What ``repro.obs.programs`` keeps of the train program of one fused
    block of a small traced-data-plane run on ``engine``."""
    sc = Scenario(model="mlp", alpha=0.2, max_dataset=120, rounds=2,
                  k_iters=2, eval_every=2, net=NetworkConfig(3, 9, 2),
                  policy="ddsra_jax", data_plane="traced", engine=engine,
                  mesh_shape=(1,) if engine == "sharded" else None)
    jax.clear_caches()                   # the call below traces anew
    obs.programs.pop("train_scan", None)
    Simulation(sc).fused_rounds()
    return obs.programs["train_scan"]


@pytest.mark.parametrize("engine", ["cohort", "sharded"])
def test_train_program_carries_the_scopes_and_no_other_change(
        monkeypatch, engine):
    hlo = _train_program(engine)
    scoped = hlo()
    names = re.findall(r'op_name="([^"]*)"', scoped)
    for scope in SCOPES:
        assert any(f"/{scope}/" in n for n in names), scope
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        jax.clear_caches()
        plain = hlo()
    jax.clear_caches()
    assert not any(f"/{s}/" in n for s in SCOPES
                   for n in re.findall(r'op_name="([^"]*)"', plain))
    assert _op_kinds(scoped) == _op_kinds(plain)
