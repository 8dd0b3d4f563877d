"""Kernel micro-benchmarks: µs/call of every implementation of the kernel
stack, each row tagged with the implementation (``ref`` / ``interpret`` /
``pallas``), the block plan the selection table chose, and its **roofline
fraction** — ``tpu_roofline_us / us_per_call``, the fraction of the device's
peak FLOP rate the measured path achieves. The peak comes from
:data:`PEAKS`, keyed by ``device_kind``; a device that is not in the table
raises, and on a CPU run both roofline columns read "not measured".

``--backward`` adds the fused_linear training-step contractions — the
transposed-operand ``dx = dz @ wᵀ`` / ``(dw, db) = (xᵀ @ dz, Σ dz)`` refs
and the end-to-end ``jax.grad`` of the custom-VJP ``linear`` op — i.e. the
two-thirds of per-step FLOPs the backward subsystem moved onto kernels.

``--autotune`` runs the block-shape sweeps (``repro.kernels.autotune``) over
the benched shapes, persists the winners into the selection tables under
``artifacts/autotune/`` and records each winner's speedup over the fixed
clamped-128 plan in an ``autotune_*`` row.

Timings accumulate into ``artifacts/benchmarks/kernel_bench.json`` (all
sections merge, so any invocation order leaves them populated).
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp

from benchmarks.common import ARTIFACTS, emit, save_json
from repro.kernels import autotune
from repro.kernels.flash_attention.ops import gqa_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.fused_linear import ops as fused_ops
from repro.kernels.fused_linear.ref import (fused_linear_bwd_dw_db_ref,
                                            fused_linear_bwd_dx_ref,
                                            fused_linear_ref)
from repro.kernels.ssd_scan.ops import ssd
from repro.kernels.ssd_scan.ref import ssd_ref

# Published per-chip peaks, keyed by jax.devices()[0].device_kind.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 819 GB/s HBM bandwidth.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud docs, TPU v5e"},
}
NOT_MEASURED = "not measured"


def _peak_flops():
    """Peak FLOP/s of the device the bench runs on; None on a CPU."""
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    if dev.device_kind not in PEAKS:
        raise KeyError(f"no published peak for device_kind "
                       f"{dev.device_kind!r}; add it to PEAKS with its source")
    return PEAKS[dev.device_kind]["flops_per_s"]

# the shapes the kernel-path section benches and --autotune sweeps; the two
# fused_linear GEMMs are deliberately non-square (the shapes where the fixed
# 128^3 plan leaves the most on the table).
GEMM_SHAPES = ((256, 512, 128), (512, 128, 256))
ATTN_SHAPE = (1, 2, 256, 64)           # (B, H, S, hd), kernel layout
SSD_SHAPE = (1, 256, 8, 64, 64)        # (B, S, n, p, ds)


def _bench(fn, *args, iters: int = 5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def _row(record: dict, name: str, us: float, flops: float, *,
         impl: str, blocks=None) -> None:
    peak = _peak_flops()
    if peak is None:
        roofline_us = frac = NOT_MEASURED
        tag = f"roofline_frac={NOT_MEASURED};impl={impl}"
    else:
        roofline_us = flops / peak * 1e6
        frac = roofline_us / us if us > 0 else 0.0
        tag = f"roofline_frac={frac:.2e};impl={impl}"
    if blocks is not None:
        tag += ";blocks=" + "x".join(str(b) for b in blocks)
    emit(name, us, tag)
    record[name] = {
        "us_per_call": us,
        "tpu_roofline_us": roofline_us,
        "roofline_frac": frac,
        "impl": impl,
        "blocks": list(blocks) if blocks is not None else None,
        "flops": flops,
    }


def _gemm_inputs(m: int, k: int, n: int, key=0, dtype=jnp.float32):
    kk = jax.random.PRNGKey(key)
    x = jax.random.normal(kk, (m, k), jnp.float32).astype(dtype)
    w = (jax.random.normal(jax.random.fold_in(kk, 1), (k, n), jnp.float32)
         / 32).astype(dtype)
    b = jnp.zeros((n,), dtype)
    return x, w, b


def _forward(record: dict) -> None:
    k = jax.random.PRNGKey(0)
    # flash attention: B=2 H=8 S=1024 D=128
    b, h, s, d = 2, 8, 1024, 128
    q, kk, v = (jax.random.normal(jax.random.fold_in(k, i), (b, h, s, d),
                                  jnp.float32) for i in range(3))
    f = jax.jit(lambda a, b_, c: attention_ref(a, b_, c, causal=True))
    flops = 4 * b * h * s * s * d / 2
    _row(record, "kernel_flash_attention_ref", _bench(f, q, kk, v), flops,
         impl="ref")

    # ssd scan: B=2 S=512 n=8 p=64 ds=64
    b2, s2, n, p, ds = 2, 512, 8, 64, 64
    xh = jax.random.normal(k, (b2, s2, n, p))
    dt = jax.nn.softplus(jax.random.normal(k, (b2, s2, n))) * 0.5
    a_log = jax.random.normal(k, (n,)) * 0.3
    bs = jax.random.normal(k, (b2, s2, ds)) * 0.5
    cs = jax.random.normal(k, (b2, s2, ds)) * 0.5
    f2 = jax.jit(ssd_ref)
    q_chunk = 128
    flops2 = b2 * s2 * n * (2 * q_chunk * p + 4 * ds * p)
    _row(record, "kernel_ssd_scan_ref", _bench(f2, xh, dt, a_log, bs, cs),
         flops2, impl="ref")

    # fused linear: 1024x1024x1024
    m = 1024
    x, w, bvec = _gemm_inputs(m, m, m)
    f3 = jax.jit(lambda a, b_, c: fused_linear_ref(a, b_, c, "relu"))
    flops3 = 2 * m**3
    _row(record, "kernel_fused_linear_ref", _bench(f3, x, w, bvec), flops3,
         impl="ref")


def _backward(record: dict) -> None:
    k = jax.random.PRNGKey(1)
    m = 1024
    gemm_flops = 2 * m**3
    x = jax.random.normal(k, (m, m))
    w = jax.random.normal(jax.random.fold_in(k, 1), (m, m)) / 32
    bvec = jnp.zeros((m,))
    dy = jax.random.normal(jax.random.fold_in(k, 2), (m, m))
    y = fused_linear_ref(x, w, bvec, "relu")

    # the two backward contractions, relu mask fused (ref = CPU hot path;
    # on TPU these become the transposed-operand Pallas kernels)
    fdx = jax.jit(lambda d, w_, y_: fused_linear_bwd_dx_ref(d, w_, y_, "relu"))
    _row(record, "kernel_fused_linear_bwd_dx_ref", _bench(fdx, dy, w, y),
         gemm_flops, impl="ref")
    fdw = jax.jit(lambda x_, d, y_: fused_linear_bwd_dw_db_ref(x_, d, y_,
                                                               "relu"))
    _row(record, "kernel_fused_linear_bwd_dw_db_ref", _bench(fdw, x, dy, y),
         gemm_flops, impl="ref")

    # end-to-end training step of the op: value+grad through the custom VJP
    # (fwd GEMM + dx + dw ≈ 3 GEMMs of work)
    fstep = jax.jit(jax.grad(
        lambda x_, w_, b_: fused_ops.linear(x_, w_, b_, activation="relu",
                                            impl="ref").sum(),
        argnums=(0, 1, 2)))
    _row(record, "kernel_fused_linear_grad_ref", _bench(fstep, x, w, bvec),
         3 * gemm_flops, impl="ref")


def _kernel_paths(record: dict) -> None:
    """Time the kernels through their real op-layer entry points — compiled
    Pallas on TPU, the Pallas interpreter elsewhere — with whatever blocks
    the op layer resolves, and tag the rows with both."""
    impl = "pallas" if jax.default_backend() == "tpu" else "interpret"
    interpret = impl == "interpret"

    for m, k, n in GEMM_SHAPES:
        x, w, b = _gemm_inputs(m, k, n)
        plan = fused_ops._plan(m, k, n, x.dtype, impl)
        blocks = (plan.block_m, plan.block_k, plan.block_n)
        fn = jax.jit(lambda a, b_, c: fused_ops.linear(a, b_, c,
                                                       activation="relu",
                                                       impl=impl))
        flops = 2 * m * k * n
        _row(record, f"kernel_fused_linear_{m}x{k}x{n}_{impl}",
             _bench(fn, x, w, b, iters=3), flops, impl=impl, blocks=blocks)

    b, h, s, d = ATTN_SHAPE
    kk = jax.random.PRNGKey(2)
    # gqa_attention takes the model layout (B, S, H, hd)
    q, kt, vt = (jax.random.normal(jax.random.fold_in(kk, i), (b, s, h, d))
                 for i in range(3))
    blocks = autotune.blocks_for("flash_attention", (b, h, s, d), "float32",
                                 interpret=interpret)
    fn = jax.jit(lambda a, b_, c: gqa_attention(a, b_, c, causal=True,
                                                interpret=interpret))
    flops = 4 * b * h * s * s * d / 2
    _row(record, f"kernel_flash_attention_{impl}",
         _bench(fn, q, kt, vt, iters=3), flops, impl=impl, blocks=blocks)

    b2, s2, n, p, ds = SSD_SHAPE
    xh = jax.random.normal(kk, (b2, s2, n, p))
    dt = jax.nn.softplus(jax.random.normal(kk, (b2, s2, n))) * 0.5
    a_log = jax.random.normal(kk, (n,)) * 0.3
    bs = jax.random.normal(kk, (b2, s2, ds)) * 0.5
    cs = jax.random.normal(kk, (b2, s2, ds)) * 0.5
    blocks = autotune.blocks_for("ssd_scan", SSD_SHAPE, "float32",
                                 interpret=interpret)
    fn = jax.jit(lambda *a: ssd(*a, interpret=interpret))
    chunk = blocks[0]
    flops2 = b2 * s2 * n * (2 * chunk * p + 4 * ds * p)
    _row(record, f"kernel_ssd_scan_{impl}",
         _bench(fn, xh, dt, a_log, bs, cs, iters=3), flops2, impl=impl,
         blocks=blocks)


def _autotune(record: dict) -> None:
    """Sweep block shapes for the benched shapes, persist the winners to the
    selection tables, and record each winner's speedup over the fixed
    clamped-128 default plan."""
    interpret = jax.default_backend() != "tpu"

    def note(name: str, entry: dict) -> None:
        if entry is None:
            return
        emit(name, entry["us"],
             f"speedup_vs_default={entry['speedup_vs_default']:.2f};"
             f"blocks=" + "x".join(str(b) for b in entry["blocks"]))
        record[name] = dict(entry)

    for m, k, n in GEMM_SHAPES:
        note(f"autotune_fused_linear_{m}x{k}x{n}",
             autotune.sweep_fused_linear(m, k, n, interpret=interpret))
    # a bf16 entry for the mixed-precision data plane's hottest shape
    m, k, n = GEMM_SHAPES[0]
    note(f"autotune_fused_linear_{m}x{k}x{n}_bf16",
         autotune.sweep_fused_linear(m, k, n, dtype="bfloat16",
                                     interpret=interpret))
    note("autotune_flash_attention",
         autotune.sweep_flash_attention(*ATTN_SHAPE, interpret=interpret))
    note("autotune_ssd_scan",
         autotune.sweep_ssd_scan(*SSD_SHAPE, interpret=interpret))


def main(fast: bool = True, backward: bool = False,
         autotune_sweep: bool = False) -> None:
    record: dict = {}
    if autotune_sweep:
        _autotune(record)
        _kernel_paths(record)      # re-times the ops at the tuned blocks
    elif backward:
        _backward(record)
    else:
        _forward(record)
        _kernel_paths(record)
    # merge with whatever section ran before, so sections accumulate
    out = ARTIFACTS / "benchmarks" / "kernel_bench.json"
    payload = json.loads(out.read_text()) if out.exists() else {}
    payload.update(record)
    save_json("kernel_bench", payload)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--backward", action="store_true",
                    help="bench the fused_linear backward contractions")
    ap.add_argument("--autotune", action="store_true",
                    help="sweep block shapes and persist the winners to "
                         "artifacts/autotune/")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    main(fast=not args.full, backward=args.backward,
         autotune_sweep=args.autotune)
