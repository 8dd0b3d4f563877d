"""Differentiable public wrapper for the fused linear kernels.

``linear`` is the training-path entry point: a ``jax.custom_vjp`` whose
forward *and* backward both run the dedicated Pallas kernels (TPU /
interpret) or their ``dot_general`` references (CPU/GPU), so the fc layers
of ``repro.models.vgg`` — and therefore the cohort split-training engine —
run the kernels directory on the hot path in both directions.

Residual policy: for ``relu``/``none`` the activation mask is recovered
from the saved *output* (``y > 0``) inside the backward kernels, so the
residuals are just ``(x, w[, y])`` and no pre-activation buffer is kept.
For smooth activations (silu/gelu) the pre-activation is rematerialized
with one extra fused GEMM in the backward pass (remat rule: one GEMM is
cheaper than holding an (M, N) buffer across the whole cohort vmap).

Routing: every contraction of the step — forward, ``dz @ wᵀ`` and
``xᵀ @ dz`` — tiles the same (M, K, N) triple, so a single
``kernel.tile_plan`` verdict decides pallas-vs-ref for the whole VJP; the
backward kernels index their transposed operand through the BlockSpec map
and never materialize ``w.T``/``x.T`` (nor does the ref path — see
``ref.py``). ``REPRO_FUSED_LINEAR_IMPL`` overrides the default impl
(e.g. ``interpret`` on CPU CI so kernel bodies actually execute).

Block sizes, in order of precedence: an exact entry of the
kernel-selection table (``repro.kernels.autotune.exact_blocks``, per shape,
dtype and backend); else ``kernel.skinny_plan`` where it applies (a slot's
batch of at most ``SKINNY_M_MAX`` rows: one row block, multi-MB weight
blocks); else the clamped-128 heuristic. The forward GEMM's (M, K, N)
triple keys the choice for all three contractions, so the whole VJP tiles
from one plan. Without a table entry the Pallas-or-ref verdict is the
clamped-128 plan's whichever plan tiles. Each traced call site that runs a
kernel bumps ``obs.counters["fused_linear.plan.<table|skinny|default>"]``.
"""
from __future__ import annotations

import functools
import os

import jax

from repro import obs
from repro.kernels import autotune
from repro.kernels.fused_linear.kernel import (TilePlan, fused_linear,
                                               fused_linear_bwd_dw_db,
                                               fused_linear_bwd_dx,
                                               skinny_plan, tile_plan)
from repro.kernels.fused_linear.ref import (ACTS, fused_linear_bwd_dw_db_ref,
                                            fused_linear_bwd_dx_ref,
                                            fused_linear_ref)

_IMPLS = ("pallas", "interpret", "ref")


def _impl_default() -> str:
    env = os.environ.get("REPRO_FUSED_LINEAR_IMPL", "")
    if env:
        if env not in _IMPLS:
            raise ValueError(f"REPRO_FUSED_LINEAR_IMPL={env!r}: "
                             f"expected one of {_IMPLS}")
        return env
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _plan(m: int, k: int, n: int, dtype, impl: str) -> TilePlan:
    """Tile plan for the whole VJP: an exact table entry, else the skinny
    rule where it applies, else the clamped-128 heuristic; ``aligned`` is
    ``tile_plan``'s verdict on the table's blocks or on the heuristic's.
    Counts, at trace time, which source tiles a kernel."""
    blocks = autotune.exact_blocks("fused_linear", (m, k, n), str(dtype),
                                   interpret=impl == "interpret")
    source = "default" if blocks is None else "table"
    bm, bk, bn = blocks or autotune.OPS["fused_linear"][0]
    plan = tile_plan(m, k, n, block_m=bm, block_n=bn, block_k=bk)
    if source == "default" and plan.aligned:
        skinny = skinny_plan(m, k, n, dtype.itemsize)
        if skinny is not None:
            source, plan = "skinny", skinny
    if impl != "ref" and plan.aligned:
        obs.count(f"fused_linear.plan.{source}")
    return plan


def _kern_kwargs(plan: TilePlan, impl: str) -> dict:
    return dict(block_m=plan.block_m, block_n=plan.block_n,
                block_k=plan.block_k, interpret=impl == "interpret")


def _matmul_act(x, w, b, activation: str, impl: str):
    """One fused forward GEMM via the chosen implementation."""
    m, k = x.shape
    n = w.shape[1]
    plan = _plan(m, k, n, x.dtype, impl)
    if impl != "ref" and plan.aligned:
        return fused_linear(x, w, b, activation=activation,
                            **_kern_kwargs(plan, impl))
    return fused_linear_ref(x, w, b, activation)


def _bwd_dx(dy, w, y, mask: str, impl: str):
    m, n = dy.shape
    plan = _plan(m, w.shape[0], n, dy.dtype, impl)
    if impl != "ref" and plan.aligned:
        return fused_linear_bwd_dx(dy, w, y, mask=mask,
                                   **_kern_kwargs(plan, impl))
    return fused_linear_bwd_dx_ref(dy, w, y, mask=mask)


def _bwd_dw_db(x, dy, y, mask: str, impl: str):
    m, n = dy.shape
    plan = _plan(m, x.shape[1], n, dy.dtype, impl)
    if impl != "ref" and plan.aligned:
        return fused_linear_bwd_dw_db(x, dy, y, mask=mask,
                                      **_kern_kwargs(plan, impl))
    return fused_linear_bwd_dw_db_ref(x, dy, y, mask=mask)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _linear_p(activation: str, impl: str, x, w, b):
    return _matmul_act(x, w, b, activation, impl)


def _linear_fwd(activation, impl, x, w, b):
    y = _matmul_act(x, w, b, activation, impl)
    if activation == "relu":
        return y, (x, w, y, None)      # mask recovered from y > 0 in bwd
    if activation == "none":
        return y, (x, w, None, None)   # identity: dz is dy, nothing extra
    return y, (x, w, None, b)          # smooth: rematerialize z in bwd


def _linear_bwd(activation, impl, res, dy):
    x, w, y, b = res
    if activation in ("relu", "none"):
        mask = activation
        dz = dy
    else:
        # remat rule: one extra fused GEMM rebuilds the pre-activation for
        # the smooth-activation derivative; dz is then plain (mask="none").
        z = _matmul_act(x, w, b, "none", impl)
        _, act_vjp = jax.vjp(ACTS[activation], z)
        (dz,) = act_vjp(dy)
        dz = dz.astype(dy.dtype)
        mask, y = "none", None
    dx = _bwd_dx(dz, w, y, mask, impl)
    dw, db = _bwd_dw_db(x, dz, y, mask, impl)
    return dx.astype(x.dtype), dw.astype(w.dtype), db.astype(dy.dtype)


_linear_p.defvjp(_linear_fwd, _linear_bwd)


def linear(x, w, b, *, activation: str = "relu", impl: str | None = None):
    """Fused ``act(x @ w + b)`` with a custom VJP in every implementation.

    ``impl``: "pallas" | "interpret" | "ref"; defaults to "pallas" on TPU
    and "ref" elsewhere (``REPRO_FUSED_LINEAR_IMPL`` overrides). The "ref"
    impl also goes through the hand-written VJP: its contractions carry the
    transposition in ``dot_general`` dimension numbers, so it matches
    autodiff cost while keeping one code path for all backends.
    """
    if impl is None:
        impl = _impl_default()
    return _linear_p(activation, impl, x, w, b)
