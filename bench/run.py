"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration,
``bench/configs/<config>.json``, and a traffic mix,
``bench/traffic/<mix>.json``, which the one general load generator in
``bench/drive.py`` reads. Set-up (the program's ``Simulation``, its stats
pass, the warm-up of the cell's own shapes and the calls the comparison
checks) is timed as ``setup_s``; then the generator calls the program back to
back for ``--seconds``. With ``--trace 1`` the window runs under the
profiler and the cell's per-layer metrics are read from the trace and the
run's counts by ``bench/metrics/<metric>.py``; with ``--trace 0`` the
end-to-end metrics are reported. After the window the program is freed and
the comparison with the plain reference (``bench/compare.py``) decides
``correct``, each number against its limit in
``bench/limits/<cell>.json``.

The last line of standard output is one JSON object. The run exits
non-zero and prints no such line without a TPU, with fewer chips than the
cell asks for, or when a ``REPRO_*_IMPL`` override asks for anything but
the Pallas kernels.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
IMPL_VARS = ("REPRO_FUSED_LINEAR_IMPL", "REPRO_FLASH_ATTENTION_IMPL",
             "REPRO_SSD_SCAN_IMPL")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
# seconds of the window a --trace 1 run records, unless the traffic mix
# sets "trace_seconds": traces are large, and writing and reading them has
# to end inside the run's time limit
TRACE_SECONDS = 3.0


class Refused(Exception):
    """The run cannot be made here; nothing is printed on stdout."""


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise Refused(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def cell_spec(name: str) -> tuple:
    """(BENCHMARK.json, the workload entry, its config, its traffic)."""
    spec = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config = load_json(BENCH / "configs" / f"{cell['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    return spec, cell, config, traffic


def cell_limits(name: str) -> dict:
    return load_json(BENCH / "limits" / f"{name}.json")["numbers"]


def metrics_for(spec: dict, cell: str, trace: bool) -> list:
    """The metric entries this cell reports in this kind of run."""
    if not trace:
        return [m for m in spec["end_to_end"]
                if cell in m.get("workloads", [cell])]
    e2e = {m["name"] for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])}
    return [m for m in spec["per_layer"]
            if m["moves"] in e2e and cell in m.get("workloads", [cell])]


def reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    if not path.is_file():
        raise Refused(f"no reader {path.relative_to(ROOT)}")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def use_cache(jax) -> None:
    """The program's persistent compilation cache
    (``repro.compile_cache.use_compile_cache``: ``JAX_COMPILATION_CACHE_DIR``
    when set, else ``<checkout>/.jax_cache``), holding every program, so
    only a checkout's first run compiles."""
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def check_device(jax, chips: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found "
                      f"{len(devices)}")
    return devices[:chips]


def judge(numbers: dict, limits: dict) -> tuple:
    """Each compared number (one with a limit) beside its limit, and
    whether all are within. A number that comes out NaN fails."""
    checks = {name: {"value": float(numbers[name]),
                     "limit": float(spec["limit"])}
              for name, spec in limits.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    return checks, bool(correct)


def run(args) -> dict:
    for var in IMPL_VARS:
        if os.environ.get(var, "pallas") != "pallas":
            raise Refused(f"{var}={os.environ[var]!r}: the benchmark runs "
                          "the Pallas kernels only; unset it")
    spec, cell, config, traffic = cell_spec(args.workload)
    limits = cell_limits(args.workload)
    wanted = metrics_for(spec, args.workload, bool(args.trace))
    readers = {m["name"]: reader(m["name"]) for m in wanted}
    if not (ROOT / "src" / "repro").is_dir():
        raise Refused("the program (src/repro) is not in this checkout")
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    use_cache(jax)
    devices = check_device(jax, int(cell["chips"]))
    from bench import drive, trace as trace_lib

    compiles, hits = [], []

    def on_event(event, duration, **kw):
        if event == COMPILE_EVENT:
            compiles.append(duration)

    def on_hit(event, **kw):
        if event == CACHE_HIT_EVENT:
            hits.append(1)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    jax.monitoring.register_event_listener(on_hit)

    t0 = time.perf_counter()
    gen = drive.make(config, traffic, args.seed)
    gen.setup()
    setup_s = time.perf_counter() - t0
    n_setup_compiles = len(compiles)

    if args.trace:
        # the profiler records the window's first seconds; the rest of
        # the window runs untraced, and the per-layer metrics read the
        # traced part alone
        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        traced_s = min(args.seconds,
                       float(traffic.get("trace_seconds", TRACE_SECONDS)))
        with drive.traced(log_dir):
            with jax.profiler.TraceAnnotation("bench.window"):
                res = drive.window(gen, traced_s)
        counts = gen.counts()
        total = dict(res)
        if args.seconds > traced_s:
            rest = drive.window(gen, args.seconds - traced_s)
            total = {k: res[k] + rest[k] for k in ("units", "failed")}
    else:
        res = total = drive.window(gen, args.seconds)
        counts = gen.counts()
    compiles_in_window = len(compiles) - n_setup_compiles

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    ctx = {"setup_s": setup_s, "res": res, "unit": gen.unit,
           "counts": counts, "stats_s": gen.stats_seconds,
           "device_kind": devices[0].device_kind, "chips": len(devices),
           "config": config, "traffic": traffic, "trace": None,
           "frac": 1.0, "units": res["units"],
           "units_per_call": res["units"] / len(res["latencies_s"])}
    breakdown = None
    trace_read_s = 0.0
    if args.trace:
        t1 = time.perf_counter()
        tr = trace_lib.load(trace_lib.find_xplane(log_dir))
        trace_read_s = time.perf_counter() - t1
        shutil.rmtree(log_dir, ignore_errors=True)
        lo, hi = trace_lib.window(tr)
        dev_ids = sorted(d for d in tr.ops if d < len(devices))
        if not dev_ids:
            raise RuntimeError("the trace holds no device operations")
        busy = [trace_lib.busy_ns(tr, d, lo, hi) for d in dev_ids]
        device["busy_s"] = sum(busy) / len(busy) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        # where the trace was cut short, the metrics read the calls that
        # ended inside what it holds
        n_calls = len(res["latencies_s"])
        inside = sum(1 for n, s, e in tr.host
                     if n == "bench.call" and lo <= s and e <= hi)
        frac = min(inside, n_calls) / n_calls
        ctx.update(trace=tr, lo=lo, hi=hi, dev_ids=dev_ids, frac=frac,
                   units=res["units"] * frac)
        busiest = dev_ids[busy.index(max(busy))]
        breakdown = {
            "device_ops": trace_lib.top_ops(tr, busiest, lo, hi),
            "idle_gaps": trace_lib.idle_gaps(tr, busiest, lo, hi)}
    metrics = {}
    for m in wanted:
        value = readers[m["name"]](ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    gen.free()
    gc.collect()
    t1 = time.perf_counter()
    numbers = gen.numbers()
    check_s = time.perf_counter() - t1
    checks, correct = judge(numbers, limits)
    correct = correct and total["failed"] == 0

    print(f"bench: setup_s={setup_s} {gen.setup_parts} stats_s="
          f"{gen.stats_seconds} compiles_in_setup={n_setup_compiles} "
          f"compile_s_in_setup={sum(compiles[:n_setup_compiles])} "
          f"cache_hits={len(hits)} compiles_in_window={compiles_in_window} "
          f"calls={len(res['latencies_s'])} window_s={res['window_s']} "
          f"check_s={check_s} trace_read_s={trace_read_s}", file=sys.stderr)
    for name, value in numbers.items():
        if name not in checks:
            print(f"reading {name}: {float(value)!r} (not compared)",
                  file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    out = {"correct": bool(correct), "attempted": int(total["units"]),
           "failed": int(total["failed"]), "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run(args)
    except Refused as e:
        print(f"bench: refused: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    sys.exit(main())
