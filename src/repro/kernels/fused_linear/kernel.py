"""Fused matmul + bias + activation Pallas TPU kernels, forward and backward.

The per-layer unit of work of the paper's split training (each partitioned
fc/conv-as-GEMM layer is exactly one of these). Three kernels share one
tiling contract (:func:`tile_plan`):

* :func:`fused_linear` — forward ``act(x @ w + b)``. Grid (M/bm, N/bn, K/bk)
  with K innermost-sequential; partial products accumulate in a VMEM fp32
  scratch; bias + activation fuse into the final K step, saving one HBM
  round-trip of the (M, N) output versus unfused matmul-then-activation.
* :func:`fused_linear_bwd_dx` — ``dx = dz @ wᵀ`` without materializing
  ``w.T``: the BlockSpec index map hands the kernel ``w`` blocks indexed
  ``(ki, ni)`` and ``dot_general`` contracts both operands on their trailing
  (N) axis, so the transpose exists only in the block-index arithmetic.
* :func:`fused_linear_bwd_dw_db` — ``dw = xᵀ @ dz`` (same trick: ``x``
  blocks indexed ``(mi, ki)``, contraction on the leading M axis) with the
  ``db = Σ_m dz`` column reduction fused into the first K-block's pass over
  M, so ``dz`` is read once for both gradients.

Both backward kernels take the *activation mask* inline (``mask="relu"``
recomputes ``dz = dy * (y > 0)`` from the saved forward output per block),
so ``dz`` is never written to HBM. Smooth activations (silu/gelu) pass a
pre-masked ``dz`` with ``mask="none"`` (see ``ops._linear_bwd``).

Inside the kernels the bias and ``db`` travel as ``(1, n)`` rows with
``(1, bn)`` blocks: Mosaic cannot match XLA's layout of a 1-D block, and a
2-D row meets the TPU's (8, 128) tiling rule. The public signatures keep
``(n,)``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_linear.ref import ACTS


class TilePlan(NamedTuple):
    """Clamped per-dimension block sizes + Pallas eligibility for one GEMM.

    The single source of truth for the block-clamping rule: each requested
    block is clamped to its dimension (a (100, 128) problem runs with a
    100-row block), and the shape is ``aligned`` — i.e. eligible for the
    Pallas kernels — iff every dimension divides evenly into its clamped
    block. Shared by the kernels (which assert it) and by the op-layer
    routing predicate in ``ops`` (which falls back to the jnp reference
    when it fails), so the two can never drift.
    """
    block_m: int
    block_k: int
    block_n: int
    aligned: bool


def tile_plan(m: int, k: int, n: int, block_m: int = 128,
              block_n: int = 128, block_k: int = 128) -> TilePlan:
    """Tiling plan for an (M, K) x (K, N) GEMM — forward or backward.

    The same (m, k, n) triple covers all three training contractions: the
    dx kernel tiles M/K as outputs and N as the reduction, the dw kernel
    tiles K/N as outputs and M as the reduction, so one predicate gates
    the whole custom-VJP path.
    """
    bm, bk, bn = min(block_m, m), min(block_k, k), min(block_n, n)
    return TilePlan(bm, bk, bn,
                    m % bm == 0 and k % bk == 0 and n % bn == 0)


def _masked_dz(dy_ref, y_ref, mask: str) -> jax.Array:
    """Recompute dz from the incoming cotangent block, in fp32.

    ``mask="relu"`` applies the activation derivative recovered from the
    saved forward *output* (``y > 0``) — the residual policy that lets the
    relu/none path keep no pre-activation buffer at all.
    """
    dz = dy_ref[...].astype(jnp.float32)
    if mask == "relu":
        dz = dz * (y_ref[...] > 0).astype(jnp.float32)
    return dz


def fused_linear(x: jax.Array, w: jax.Array, b: jax.Array,
                 *, activation: str = "relu", block_m: int = 128,
                 block_n: int = 128, block_k: int = 128,
                 interpret: bool = False) -> jax.Array:
    """x (M, K) @ w (K, N) + b (N,), activation fused. MXU-aligned tiles."""
    m, k = x.shape
    _, n = w.shape
    plan = tile_plan(m, k, n, block_m, block_n, block_k)
    assert plan.aligned, (m, k, n, plan)
    bm, bk, bn = plan.block_m, plan.block_k, plan.block_n

    def kernel(x_ref, w_ref, b_ref, o_ref, acc_scr):
        ki = pl.program_id(2)

        @pl.when(ki == 0)
        def _init():
            acc_scr[...] = jnp.zeros_like(acc_scr)

        acc_scr[...] += jax.lax.dot_general(
            x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

        @pl.when(ki == pl.num_programs(2) - 1)
        def _finalize():
            y = acc_scr[...] + b_ref[...].astype(jnp.float32)
            o_ref[...] = ACTS[activation](y).astype(o_ref.dtype)

    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
            pl.BlockSpec((1, bn), lambda mi, ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w, b.reshape(1, n))


def fused_linear_bwd_dx(dy: jax.Array, w: jax.Array, y: jax.Array | None = None,
                        *, mask: str = "none", block_m: int = 128,
                        block_n: int = 128, block_k: int = 128,
                        interpret: bool = False) -> jax.Array:
    """dx (M, K) = (dy ⊙ mask(y)) @ wᵀ with no materialized ``w.T``.

    Grid (M/bm, K/bk, N/bn), N innermost-sequential: ``w`` blocks are
    fetched at block index ``(ki, ni)`` — the transposed-operand trick —
    and ``dot_general`` contracts dz's and w's trailing N axes directly.
    """
    m, n = dy.shape
    k = w.shape[0]
    plan = tile_plan(m, k, n, block_m, block_n, block_k)
    assert plan.aligned, (m, k, n, plan)
    assert mask == "none" or y is not None
    bm, bk, bn = plan.block_m, plan.block_k, plan.block_n

    def kernel(*refs):
        dy_ref, y_ref = (refs[0], refs[1]) if mask != "none" else (refs[0], None)
        w_ref, o_ref, acc_scr = refs[-3:]
        ni = pl.program_id(2)

        @pl.when(ni == 0)
        def _init():
            acc_scr[...] = jnp.zeros_like(acc_scr)

        dz = _masked_dz(dy_ref, y_ref, mask)
        # dz (bm, bn) · w (bk, bn) contracted on N -> (bm, bk): w enters in
        # its stored layout; only its *block index* is transposed.
        acc_scr[...] += jax.lax.dot_general(
            dz, w_ref[...].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

        @pl.when(ni == pl.num_programs(2) - 1)
        def _finalize():
            o_ref[...] = acc_scr[...].astype(o_ref.dtype)

    in_specs = [pl.BlockSpec((bm, bn), lambda mi, ki, ni: (mi, ni))]
    operands = [dy]
    if mask != "none":
        in_specs.append(pl.BlockSpec((bm, bn), lambda mi, ki, ni: (mi, ni)))
        operands.append(y)
    in_specs.append(pl.BlockSpec((bk, bn), lambda mi, ki, ni: (ki, ni)))
    operands.append(w)

    return pl.pallas_call(
        kernel,
        grid=(m // bm, k // bk, n // bn),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bk), lambda mi, ki, ni: (mi, ki)),
        out_shape=jax.ShapeDtypeStruct((m, k), dy.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32)],
        interpret=interpret,
    )(*operands)


def fused_linear_bwd_dw_db(x: jax.Array, dy: jax.Array,
                           y: jax.Array | None = None, *, mask: str = "none",
                           block_m: int = 128, block_n: int = 128,
                           block_k: int = 128,
                           interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """(dw, db) = (xᵀ @ dz, Σ_m dz) in one pass, no materialized ``x.T``.

    Grid (N/bn, K/bk, M/bm), M innermost-sequential: ``x`` blocks are
    fetched at ``(mi, ki)`` and contracted with dz on their *leading* M
    axis. The db column reduction rides along in the ki == 0 sweep over M
    (each dz block is already in VMEM there), so dz is materialized for
    neither gradient. N is the outermost grid axis so the db output block
    stays resident across the whole (ki, mi) inner loop.
    """
    m, n = dy.shape
    k = x.shape[1]
    plan = tile_plan(m, k, n, block_m, block_n, block_k)
    assert plan.aligned, (m, k, n, plan)
    assert mask == "none" or y is not None
    bm, bk, bn = plan.block_m, plan.block_k, plan.block_n

    def kernel(*refs):
        x_ref = refs[0]
        dy_ref, y_ref = (refs[1], refs[2]) if mask != "none" else (refs[1], None)
        dw_ref, db_ref, acc_scr, db_scr = refs[-4:]
        ki, mi = pl.program_id(1), pl.program_id(2)
        nm = pl.num_programs(2)

        @pl.when(mi == 0)
        def _init():
            acc_scr[...] = jnp.zeros_like(acc_scr)

        @pl.when(jnp.logical_and(ki == 0, mi == 0))
        def _init_db():
            db_scr[...] = jnp.zeros_like(db_scr)

        dz = _masked_dz(dy_ref, y_ref, mask)
        # x (bm, bk) · dz (bm, bn) contracted on M -> (bk, bn)
        acc_scr[...] += jax.lax.dot_general(
            x_ref[...].astype(jnp.float32), dz,
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

        @pl.when(ki == 0)
        def _db_accum():
            db_scr[...] += jnp.sum(dz, axis=0, keepdims=True)

        @pl.when(mi == nm - 1)
        def _finalize():
            dw_ref[...] = acc_scr[...].astype(dw_ref.dtype)

        @pl.when(jnp.logical_and(ki == 0, mi == nm - 1))
        def _finalize_db():
            db_ref[...] = db_scr[...].astype(db_ref.dtype)

    in_specs = [pl.BlockSpec((bm, bk), lambda ni, ki, mi: (mi, ki)),
                pl.BlockSpec((bm, bn), lambda ni, ki, mi: (mi, ni))]
    operands = [x, dy]
    if mask != "none":
        in_specs.append(pl.BlockSpec((bm, bn), lambda ni, ki, mi: (mi, ni)))
        operands.append(y)

    dw, db = pl.pallas_call(
        kernel,
        grid=(n // bn, k // bk, m // bm),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bk, bn), lambda ni, ki, mi: (ki, ni)),
            pl.BlockSpec((1, bn), lambda ni, ki, mi: (0, ni)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, n), x.dtype),
            jax.ShapeDtypeStruct((1, n), dy.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32),
                        pltpu.VMEM((1, bn), jnp.float32)],
        interpret=interpret,
    )(*operands)
    return dw, db.reshape(n)
