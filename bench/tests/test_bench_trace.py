"""The trace reduction (bench/trace.py) on synthetic traces and on one
recorded on the CPU."""
import jax
import jax.numpy as jnp
import pytest

from bench import drive, trace

MS = 1_000_000


def synthetic():
    # device 0: ops at [0,2] [1,3] [5,6] ms; programs decide [0,3], train [5,6]
    ops = {0: [("fusion.1", 0, 2 * MS), ("fusion.2", 1 * MS, 3 * MS),
               ("convolution.3", 5 * MS, 6 * MS)],
           1: [("fusion.1", 0, 10 * MS)]}
    modules = {0: [("jit__decide_scan(1)", 0, 3 * MS),
                   ("jit_train_scan_traced(2)", 5 * MS, 6 * MS)],
               1: [("jit_train_scan_traced(2)", 0, 10 * MS)]}
    host = [("bench.window", 0, 10 * MS), ("bench.call", 0, 6 * MS),
            ("bench.reset", 7 * MS, 9 * MS)]
    return trace.Trace(ops, modules, host)


def test_union_merges_overlaps_and_clips():
    got = trace.union([(5, 8), (0, 2), (1, 3), (9, 20)], 0, 10)
    assert got == [(0, 3), (5, 8), (9, 10)]
    assert trace.gaps(got, 0, 12) == [(3, 5), (8, 9), (10, 12)]


@pytest.mark.parametrize("dev,busy_ms,idle", [(0, 4, 0.6), (1, 10, 0.0)])
def test_busy_and_idle_share(dev, busy_ms, idle):
    tr = synthetic()
    lo, hi = trace.window(tr)
    assert trace.busy_ns(tr, dev, lo, hi) == busy_ms * MS
    assert trace.idle_share(tr, lo, hi)[dev] == pytest.approx(idle)


@pytest.mark.parametrize("pattern,ms,n", [(r"decide_scan", 3, 1),
                                          (r"train_scan", 1, 1),
                                          (r"policy_sweep", 0, 0)])
def test_program_time(pattern, ms, n):
    tr = synthetic()
    assert trace.whole_executions(tr, 0, pattern, 0, 10 * MS) == (ms * MS,
                                                                  n)


def test_program_time_is_clipped_to_the_window():
    # an execution the window cuts is left out whole
    tr = synthetic()
    assert trace.whole_executions(tr, 0, "decide", 2 * MS, 10 * MS) == (0, 0)
    assert trace.whole_executions(tr, 0, "scan", 0, 4 * MS) == (3 * MS, 1)


def test_idle_gaps_are_named_by_host_spans():
    tr = synthetic()
    got = trace.idle_gaps(tr, 0, 0, 10 * MS)
    # gaps [6,10] (around the reset), [3,5] (inside the call)
    assert got == [["bench.reset", 0.004], ["bench.call", 0.002]]


def test_top_ops_and_ops_time():
    tr = synthetic()
    top = trace.top_ops(tr, 0, 0, 10 * MS, k=2)
    assert top == [["fusion.1", 0.002], ["fusion.2", 0.002]]
    conv = trace.ops_ns(tr, 0, lambda n: n.startswith("conv"), 0, 10 * MS)
    assert conv == 1 * MS


def test_window_requires_the_span():
    tr = trace.Trace({}, {}, [])
    with pytest.raises(ValueError):
        trace.window(tr)


def test_recorded_cpu_trace_keeps_the_benchmark_spans(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with drive.traced(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(2):
                with jax.profiler.TraceAnnotation("bench.call"):
                    f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("not.ours"):
                pass
    tr = trace.load(trace.find_xplane(tmp_path))
    names = [n for n, _, _ in tr.host]
    assert names.count("bench.window") == 1
    assert names.count("bench.call") == 2
    assert "not.ours" not in names
    lo, hi = trace.window(tr)
    calls = [(s, e) for n, s, e in tr.host if n == "bench.call"]
    assert all(lo <= s <= e <= hi for s, e in calls)


def test_window_stops_where_the_device_buffer_overflowed():
    tr = synthetic()
    tr.dropped_from = 4 * MS           # no call ends before it
    assert trace.window(tr) == (0, 4 * MS)
    lo, hi = trace.window(tr)
    assert trace.idle_share(tr, lo, hi)[0] == pytest.approx(0.25)


def test_cut_window_ends_with_the_last_whole_call():
    tr = synthetic()
    tr.host += [("bench.call", 6 * MS, 7 * MS)]
    tr.dropped_from = 8 * MS           # the reset span is not a call
    assert trace.window(tr) == (0, 7 * MS)


@pytest.mark.parametrize("name,container,kernel", [
    ("%while.154 = (s32[], f32[64]) while((s32[], f32[64]) %t)", True,
     False),
    ("%cond.3 = f32[2] conditional(pred[] %p, f32[2] %a)", True, False),
    ("%vmap_jvp___.16 = f32[6,89,4096] custom-call(f32[6,89,512] %x)",
     False, True),
    ("%fusion.10 = (f32[648], f32[648]) fusion(f32[648] %r)", False, False),
])
def test_hlo_event_kinds(name, container, kernel):
    assert trace.is_container(name) == container
    assert trace.is_kernel(name) == kernel


def test_op_names_keep_the_instruction_kind():
    assert trace.op_name("%fusion.12 = f32[6,4096]{1,0:T(8,128)} "
                         "fusion(f32[6] %a), kind=kLoop") == "fusion.12 fusion"
    assert trace.op_name("%custom-call.3 = f32[8]{0} custom-call(f32[8] %x)"
                         ) == "custom-call.3 custom-call"
    assert trace.op_name("fusion.1") == "fusion.1"

