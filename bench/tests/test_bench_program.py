"""The readings of the program's own instrumentation (bench/program.py and
the readers that use it) on synthetic traces and records, and the
existing readers' values left as they were beside them."""
import collections
import sys
import types

import pytest

from bench import program, run as bench_run, trace

MS = 1_000_000
META = 'metadata={{op_name="jit(train_scan_traced)/while/body/{}"}}'

# a train program's optimized HLO: one instruction per scope, a fused
# computation, and instructions without a scope of their own: one read by
# a scoped instruction, one reading one, and one with no scoped neighbour
HLO = "\n".join([
    "HloModule jit_train_scan_traced, entry_computation_layout={()->()}",
    "%fused_computation.1 (param_0: f32[8]) -> f32[8] {",
    "  %param_0 = f32[8]{0} parameter(0)",
    "  ROOT %add.7 = f32[8]{0} add(f32[8]{0} %param_0, f32[8]{0} %param_0),"
    " " + META.format("local_sgd/jvp/add"),
    "}",
    "ENTRY %main.9 () -> f32[8] {",
    "  %gather.3 = f32[8]{0} gather(f32[8]{0} %x, s32[8]{0} %i), "
    + META.format("gather/vmap/gather"),
    "  %reverse.8 = f32[8]{0} reverse(f32[8]{0} %gather.3), "
    "dimensions={0}",
    "  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %reverse.8), kind=kLoop, "
    "calls=%fused_computation.1, " + META.format("local_sgd/jvp/add"),
    "  %jvp__.16 = f32[8]{0} custom-call(f32[8]{0} %fusion.1), "
    'custom_call_target="tpu_custom_call", '
    + META.format("local_sgd/vmap/jvp/pallas_call"),
    "  %fusion.2 = f32[8]{0} fusion(f32[8]{0} %jvp__.16), kind=kLoop, "
    + META.format("fedavg/dot_general"),
    "  %fusion.3 = s32[] fusion(f32[8]{0} %fusion.2), kind=kLoop, "
    + META.format("eval/cond/branch_1_fun/reduce_sum"),
    "  %dynamic-update-slice.5 = f32[4]{0} dynamic-update-slice(f32[4]{0} "
    "%a, f32[1]{0} %b, s32[] %c), " + META.format("dynamic_update_slice"),
    "  %copy.4 = f32[8]{0} copy(f32[8]{0} %fusion.2)",
    "  ROOT %while.6 = (s32[], f32[8]) while((s32[], f32[8]) %t), "
    "condition=%c, body=%b",
    "}",
])


def _op(text, s_ms, e_ms):
    return (text, int(s_ms * MS), int(e_ms * MS))


def synthetic():
    """A decide program [0, 3] ms, then a train program [10, 20] ms whose
    operations are the HLO's; the decide program reuses one name."""
    ops = {0: [
        _op("%fusion.1 = f32[2]{0} fusion(f32[2]{0} %q), kind=kLoop", 0, 3),
        _op("%while.6 = (s32[], f32[8]) while((s32[], f32[8]) %t)", 10, 20),
        _op("%gather.3 = f32[8]{0} gather(f32[8]{0} %x, s32[8]{0} %i)",
            10, 11),
        _op("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %gather.3), "
            "kind=kLoop", 11, 15),
        _op("%jvp__.16 = f32[8]{0} custom-call(f32[8]{0} %fusion.1)", 15, 17),
        _op("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %jvp__.16)", 17, 18),
        _op("%fusion.3 = s32[] fusion(f32[8]{0} %fusion.2)", 18, 18.5),
        _op("%dynamic-update-slice.5 = f32[4]{0} dynamic-update-slice("
            "f32[4]{0} %a)", 18.5, 19),
        _op("%copy.4 = f32[8]{0} copy(f32[8]{0} %fusion.2)", 19, 19.5)]}
    modules = {0: [("jit__decide_scan(1)", 0, 3 * MS),
                   ("jit_train_scan_traced(2)", 10 * MS, 20 * MS)]}
    host = [("bench.window", 0, 30 * MS), ("bench.call", 0, 21 * MS)]
    return trace.Trace(ops, modules, host)


def fused_ctx(tr=None):
    tr = synthetic() if tr is None else tr
    lo, hi = trace.window(tr)
    return {"setup_s": 20.5, "stats_s": 4.5, "unit": "rounds",
            "res": {"units": 8, "window_s": 0.8,
                    "latencies_s": [0.39, 0.41]},
            "counts": {"real_samples": 1000.0, "padded_samples": 2280.0,
                       "trained_rounds": 4, "slots": 6, "evals": 1,
                       "eval_rows": 1000, "k_iters": 5, "width_mult": 1.0,
                       "classes": 10},
            "device_kind": "TPU v5 lite", "chips": 1,
            "traffic": {"call": "fused_rounds", "check_calls": 3},
            "trace": tr, "lo": lo, "hi": hi, "dev_ids": [0], "frac": 1.0,
            "units": 4, "units_per_call": 4}


def rec(name, seq, seconds, parts, **attrs):
    return types.SimpleNamespace(name=name, seq=seq, seconds=seconds,
                                 parts=parts, attrs=attrs)


FUSED_PARTS = {"repro.fused.draw": 0.001, "repro.fused.decide.dispatch":
               0.002, "repro.fused.decide.wait": 0.010,
               "repro.fused.pack": 0.003, "repro.fused.train.dispatch":
               0.004, "repro.fused.train.wait": 0.350,
               "repro.fused.records": 0.002}


@pytest.fixture
def fake_obs(monkeypatch):
    """The program's repro.obs with a train program, calls and counters."""
    from repro import obs
    monkeypatch.setitem(obs.programs, "train_scan", lambda: HLO)
    calls = collections.deque(
        [rec("repro.setup.stats", 1, 4.5, {})]
        + [rec("repro.fused", i, 0.375, dict(FUSED_PARTS), block=4 * i,
               rounds=4) for i in range(1, 6)]
        + [rec("repro.sweep", i, 0.31, {"repro.sweep.draw": 0.004,
                                        "repro.sweep.wait": 0.29},
               lanes=36, rounds=50) for i in (1, 2, 3)])
    monkeypatch.setattr(obs, "calls", calls)
    monkeypatch.setattr(obs, "counters", collections.defaultdict(float, {
        "compile.repro.fused.train.dispatch.trace_s": 2.0,
        "compile.repro.fused.train.dispatch.lower_s": 1.0,
        "compile.repro.fused.train.dispatch.backend_s": 3.0,
        "compile.repro.fused.train.dispatch.n": 1.0,
        "compile.unspanned.backend_s": 0.5,
        "compile.bench.read.scopes.backend_s": 7.0,
        "trace.cohort.train_scan": 1.0}))
    return obs


@pytest.fixture
def no_obs(monkeypatch):
    """A program without repro.obs, as the parent commit's is."""
    import repro
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    monkeypatch.delattr(repro, "obs", raising=False)


def read(name, ctx):
    return bench_run.reader(name)(ctx)


def test_hlo_scopes_take_the_innermost_named_scope_or_a_neighbours():
    got = program.hlo_scopes(HLO)
    assert got["gather.3"] == "gather"
    assert got["fusion.1"] == got["jvp__.16"] == got["add.7"] == "local_sgd"
    assert got["fusion.2"] == "fedavg"
    assert got["fusion.3"] == "eval"
    # no scope of its own: that of its reader, else of what it reads
    assert got["reverse.8"] == "local_sgd"
    assert got["copy.4"] == "fedavg"
    assert got["dynamic-update-slice.5"] == got["while.6"] == ""


def test_scope_ns_reads_whole_train_executions_only():
    tr = synthetic()
    by, n = program.scope_ns(tr, 0, 0, 30 * MS, program.hlo_scopes(HLO))
    assert n == 1
    # the decide program's fusion.1 and the while loop are not counted
    assert by == {"gather": 1 * MS, "local_sgd": 6 * MS,
                  "fedavg": 3 * MS // 2, "eval": MS // 2, "": MS // 2}
    # a window that cuts the execution reads nothing of it
    assert program.scope_ns(tr, 0, 12 * MS, 30 * MS,
                            program.hlo_scopes(HLO)) == ({}, 0)


@pytest.mark.parametrize("name,ms", [("gather_ms.fused", 0.25),
                                     ("local_sgd_ms.fused", 1.5),
                                     ("fedavg_ms.fused", 0.375),
                                     ("eval_ms.fused", 0.125)])
def test_scope_readers(fake_obs, name, ms):
    ctx = fused_ctx()
    assert read(name, ctx) == pytest.approx(ms)
    # the train program is compiled again once for all four readers
    assert "program_scopes" in ctx


def test_scope_reader_reads_zero_for_a_scope_that_did_not_run(fake_obs):
    tr = synthetic()
    tr.ops[0] = [op for op in tr.ops[0] if "%fusion.3 " not in op[0]]
    assert read("eval_ms.fused", fused_ctx(tr)) == 0.0


@pytest.mark.parametrize("name", ["gather_ms.fused", "local_sgd_ms.fused",
                                  "fedavg_ms.fused", "eval_ms.fused"])
def test_scope_readers_read_none_without_what_they_need(fake_obs, no_obs,
                                                        monkeypatch, name):
    assert read(name, fused_ctx()) is None                 # no repro.obs
    monkeypatch.undo()
    assert read(name, dict(fused_ctx(), trace=None)) is None
    assert read(name, dict(fused_ctx(), unit="lane-rounds")) is None
    from repro import obs
    monkeypatch.setattr(obs, "programs", {})
    assert read(name, fused_ctx()) is None                 # no program
    monkeypatch.setattr(obs, "programs", {"train_scan": lambda: "\n".join(
        line.split(", metadata")[0] for line in HLO.splitlines())})
    assert read(name, fused_ctx()) is None                 # no scopes
    tr = synthetic()
    tr.modules[0] = tr.modules[0][:1]
    monkeypatch.setattr(obs, "programs", {"train_scan": lambda: HLO})
    assert read(name, fused_ctx(tr)) is None               # no execution


def test_host_ms_fused_reads_the_window_calls(fake_obs):
    # calls 4 and 5 come after set-up's three: 0.375 - 0.360 s of host
    # work each, over 4 rounds each
    assert read("host_ms.fused", fused_ctx()) == pytest.approx(3.75)


def test_host_us_lane_round_reads_the_window_calls(fake_obs):
    ctx = dict(fused_ctx(), unit="lane-rounds",
               traffic={"call": "sweep"})
    # calls 2 and 3 after set-up's one: 0.02 s each over 36 x 50
    assert read("host_us.lane_round", ctx) == pytest.approx(
        1e6 * 0.02 / 1800)
    assert read("host_ms.fused", ctx) is None


def test_host_readers_read_none_without_window_calls(fake_obs, monkeypatch):
    monkeypatch.setattr(fake_obs, "calls", collections.deque(
        c for c in fake_obs.calls if c.seq <= 1))
    assert read("host_ms.fused", fused_ctx()) is None
    ctx = dict(fused_ctx(), unit="lane-rounds", traffic={"call": "sweep"})
    assert read("host_us.lane_round", ctx) is None


def test_jit_s_sums_the_program_compiles(fake_obs):
    # trace, lower and backend seconds of every span and outside them;
    # counts, trace counters and the benchmark's own compiles left out
    assert read("jit_s", fused_ctx()) == pytest.approx(6.5)


@pytest.mark.parametrize("name", ["host_ms.fused", "host_us.lane_round",
                                  "jit_s"])
def test_program_readers_read_none_without_repro_obs(no_obs, name):
    ctx = fused_ctx()
    if name == "host_us.lane_round":
        ctx = dict(ctx, unit="lane-rounds", traffic={"call": "sweep"})
    assert read(name, ctx) is None


EXISTING = ["setup_s", "rounds_per_s", "block_ms.p90", "decide_ms.fused",
            "train_ms.fused", "mfu", "pad_share.fused",
            "fused_linear_roofline", "idle_share.fused", "stats_s"]


def test_program_spans_and_scopes_leave_existing_readers_unchanged(
        monkeypatch):
    before = {n: read(n, fused_ctx()) for n in EXISTING}
    assert all(v is not None for v in before.values()), before
    from repro import obs
    monkeypatch.setitem(obs.programs, "train_scan", lambda: HLO)
    tr = synthetic()
    tr.host += [("repro.fused", 1 * MS, 20 * MS),
                ("repro.fused.decide.wait", 2 * MS, 3 * MS),
                ("repro.fused.pack", 3 * MS, 5 * MS),
                ("repro.fused.train.dispatch", 5 * MS, 10 * MS),
                ("repro.fused.train.wait", 10 * MS, 20 * MS)]
    ctx = fused_ctx(tr)
    for name in ("gather_ms.fused", "eval_ms.fused"):
        assert read(name, ctx) is not None
    after = {n: read(n, ctx) for n in EXISTING}
    assert after == before
    assert trace.window(tr) == trace.window(synthetic())
