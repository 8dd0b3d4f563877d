"""Benchmark harness: one entry per paper table/figure + roofline report.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME] [--list]

Prints ``name,us_per_call,derived`` CSV lines (plus human-readable detail).
"""
from __future__ import annotations

import argparse
import sys
import traceback

# (name, module, extra main() kwargs, description) — `--only NAME` and
# `--list` use the name; several names may share one module.
BENCHES = [
    ("table2_costmodel", "table2_costmodel", {},
     "Table II layer-level FLOPs model vs XLA"),
    ("kernel_bench", "kernel_bench", {},
     "Pallas-kernel reference micro-benchmarks (forward)"),
    ("kernel_bench --backward", "kernel_bench", {"backward": True},
     "fused_linear backward (dx / dw+db / grad) micro-benchmarks"),
    ("kernel_bench --autotune", "kernel_bench", {"autotune_sweep": True},
     "block-shape sweeps -> artifacts/autotune selection tables"),
    ("fl_round_bench", "fl_round_bench", {},
     "Cohort engine vs sequential FL round (speedup)"),
    ("fl_round_bench --churn", "fl_round_bench", {"churn_sweep": True},
     "churn/straggler sweep: sync barrier vs buffered async delay"),
    ("fl_round_bench --fused", "fl_round_bench", {"fused_sweep": True},
     "fused scan-the-round-loop vs stepwise rounds/sec + sweep farm"),
    ("fl_round_bench --model vgg", "fl_round_bench", {"model": "vgg"},
     "model-zoo round bench: VGG-11 (the paper's model)"),
    ("fl_round_bench --model transformer", "fl_round_bench",
     {"model": "transformer"},
     "model-zoo round bench: GQA decoder on the flash-attention path"),
    ("fl_round_bench --model ssm", "fl_round_bench", {"model": "ssm"},
     "model-zoo round bench: Mamba-2/SSD decoder"),
    ("scheduler_bench", "scheduler_bench", {},
     "DDSRA decide latency: numpy oracle vs jitted control plane"),
    ("theorem2_tradeoff", "theorem2_tradeoff", {},
     "Theorem 2 [O(1/V), O(sqrt V)] trade-off"),
    ("fig2_participation", "fig2_participation", {},
     "Fig 2 derived vs experimental participation"),
    ("fig456_schedulers", "fig456_schedulers", {},
     "Figs 4-6 DDSRA vs baselines"),
    ("roofline_report", "roofline_report", {},
     "Roofline table from dry-run artifacts"),
]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true",
                    help="full-size runs (slower, closer to paper scale)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--list", action="store_true",
                    help="list benchmark names and exit")
    args = ap.parse_args()

    if args.list:
        for name, _, _, desc in BENCHES:
            print(f"{name:24s} {desc}")
        return
    if args.only and args.only not in {name for name, _, _, _ in BENCHES}:
        ap.error(f"unknown benchmark {args.only!r} (see --list)")

    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    failures = []
    for name, mod_name, kwargs, desc in BENCHES:
        if args.only and args.only != name:
            continue
        print(f"# {name}: {desc}", flush=True)
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["main"])
            mod.main(fast=not args.full, **kwargs)
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            failures.append(name)
    if failures:
        print(f"FAILED: {failures}")
        sys.exit(1)


if __name__ == "__main__":
    main()
