"""Roofline report: aggregates artifacts/dryrun/*.json into the per-
(arch x shape x mesh) table consumed by EXPERIMENTS.md §Roofline, plus a
kernel-stack section that converts ``kernel_bench.json`` rows into roofline
*fractions* (``tpu_roofline_us / us_per_call``). Rows timed on a CPU carry
"not measured" in both roofline columns: a CPU or interpreter time is not a
device number."""
from __future__ import annotations

import json
import pathlib

from benchmarks.common import ARTIFACTS, emit, save_json
from benchmarks.kernel_bench import NOT_MEASURED

DRYRUN = ARTIFACTS / "dryrun"
KERNEL_BENCH = ARTIFACTS / "benchmarks" / "kernel_bench.json"


def load_all():
    rows = []
    for f in sorted(DRYRUN.glob("*.json")):
        rows.append(json.loads(f.read_text()))
    return rows


def to_markdown(rows, mesh: str = "16x16") -> str:
    lines = [
        "| arch | shape | t_comp (s) | t_mem (s) | t_coll (s) | bound | "
        "useful frac | HBM/dev (GiB) |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        if r["mesh"] != mesh:
            continue
        ro, m = r["roofline"], r["memory"]
        lines.append(
            f"| {r['arch']} | {r['shape']} | {ro['t_compute_s']:.2e} | "
            f"{ro['t_memory_s']:.2e} | {ro['t_collective_s']:.2e} | "
            f"{ro['bottleneck']} | {r['useful_flops_frac']:.2f} | "
            f"{m['peak_bytes']/2**30:.2f} |")
    return "\n".join(lines)


def kernel_fractions() -> list:
    """Per-row roofline fractions from ``kernel_bench.json`` (rows written
    before the tagging scheme — plain us/roofline pairs — are upgraded on
    the fly; ``autotune_*`` rows report speedup instead)."""
    if not KERNEL_BENCH.exists():
        return []
    payload = json.loads(KERNEL_BENCH.read_text())
    out = []
    for name, row in sorted(payload.items()):
        if not isinstance(row, dict):
            continue
        if "us_per_call" not in row and "us" not in row:
            continue
        us = float(row.get("us_per_call", row.get("us", 0.0)))
        out.append({
            "name": name,
            "impl": row.get("impl", "ref"),
            "blocks": row.get("blocks"),
            "us_per_call": us,
            # "not measured" unless the row came from a run on a device
            # with a known peak (benchmarks.kernel_bench.PEAKS)
            "tpu_roofline_us": row.get("tpu_roofline_us", NOT_MEASURED),
            "roofline_frac": row.get("roofline_frac", NOT_MEASURED),
            "speedup_vs_default": row.get("speedup_vs_default"),
        })
    return out


def _num(v, fmt: str) -> str:
    return v if isinstance(v, str) else format(v, fmt)


def kernels_markdown(rows: list) -> str:
    lines = [
        "| kernel row | impl | blocks | µs/call | TPU roofline µs | "
        "roofline frac | autotune speedup |",
        "|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        blocks = "x".join(str(b) for b in r["blocks"]) if r["blocks"] else "—"
        sp = f"{r['speedup_vs_default']:.2f}x" \
            if r.get("speedup_vs_default") else "—"
        lines.append(
            f"| {r['name']} | {r['impl']} | {blocks} | "
            f"{r['us_per_call']:.1f} | {_num(r['tpu_roofline_us'], '.2f')} | "
            f"{_num(r['roofline_frac'], '.2e')} | {sp} |")
    return "\n".join(lines)


def main(fast: bool = True):
    rows = load_all()
    if rows:
        n1 = sum(r["mesh"] == "16x16" for r in rows)
        n2 = sum(r["mesh"] == "2x16x16" for r in rows)
        bounds = {}
        for r in rows:
            if r["mesh"] == "16x16":
                bounds[r["roofline"]["bottleneck"]] = bounds.get(
                    r["roofline"]["bottleneck"], 0) + 1
        save_json("roofline_rows", rows)
        (ARTIFACTS / "roofline_16x16.md").write_text(to_markdown(rows))
        (ARTIFACTS / "roofline_2x16x16.md").write_text(
            to_markdown(rows, "2x16x16"))
        emit("roofline_table", 0.0,
             f"1pod={n1}/40;2pod={n2}/40;bounds={bounds}")
    else:
        emit("roofline_table", 0.0, "no dryrun artifacts yet")

    krows = kernel_fractions()
    if krows:
        save_json("roofline_kernels", krows)
        (ARTIFACTS / "roofline_kernels.md").write_text(
            kernels_markdown(krows) + "\n")
        tuned = [r for r in krows if r.get("speedup_vs_default")]
        emit("roofline_kernels", 0.0,
             f"rows={len(krows)};tuned={len(tuned)}")
    else:
        emit("roofline_kernels", 0.0, "no kernel_bench artifact yet")


if __name__ == "__main__":
    main()
