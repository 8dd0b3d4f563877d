"""Flash attention Pallas TPU kernels (online softmax, VMEM-tiled): forward
plus the dedicated backward pair.

Forward grid: (batch*heads, num_q_blocks, num_k_blocks); the k axis is
innermost and sequential on TPU, so the running max / denominator /
accumulator live in VMEM scratch across k steps (the canonical flash
recurrence). Block shapes are MXU-aligned (multiples of 128 on the lane dim;
block_q/block_k sublane). The forward also emits the per-row log-sum-exp so
the backward kernels can rebuild the probabilities without a second online
pass. The per-row lse and ``delta`` travel as ``(batch*heads, S, 1)``
columns with ``(1, block_q, 1)`` blocks: the block's last two dims then
meet the TPU's (8, 128) tiling rule, and each kernel reads them directly as
the ``(block_q, 1)`` column its broadcasts need.

Backward follows the standard two-kernel split (dq separately from dk/dv) so
each kernel accumulates over exactly one sequential grid axis:

* ``dq``:   grid (bh, nq, nk), k innermost — dq_scr accumulates over k blocks;
* ``dkdv``: grid (bh, nk, nq), q innermost — dk/dv scratch accumulate over q.

Both rebuild ``p = exp(s - lse)`` from the saved lse, and carry every operand
transposition in ``dot_general`` dimension numbers (``dvᵀ = pᵀ @ do`` and
``dk = dsᵀ @ q`` contract the shared *leading* axis) — the same
transposed-operand recipe as the fused_linear backward kernels: no
materialized transposes anywhere in the training jaxpr.

Causal + sliding-window masking is applied inside the block; fully-masked
blocks still execute (grid is static) but contribute nothing — ``ops.py``
documents the cost model.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _block_mask(qi, ki, *, block_q: int, block_k: int,
                causal: bool, window: Optional[int], seq_len: int):
    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = kpos < seq_len
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    return mask


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                  m_scr, l_scr, acc_scr,
                  *, scale: float, block_q: int, block_k: int,
                  causal: bool, window: Optional[int], seq_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                 # (bq, d)
    k = k_ref[0].astype(jnp.float32)                 # (bk, d)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    mask = _block_mask(qi, ki, block_q=block_q, block_k=block_k,
                       causal=causal, window=window, seq_len=seq_len)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                              # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                           # (bq, bk)
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(denom)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    *, causal: bool = True, window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False, return_lse: bool = False):
    """q, k, v: (B, H, S, D) with equal head counts -> (B, H, S, D).

    With ``return_lse=True`` also returns the per-row log-sum-exp
    ``lse = m + log(l)`` of shape (B, H, S) — the residual the backward
    kernels need to rebuild the softmax without a second online pass.
    """
    b, h, s, d = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    bh = b * h
    qf = q.reshape(bh, s, d)
    kf = k.reshape(bh, s, d)
    vf = v.reshape(bh, s, d)
    grid = (bh, s // block_q, s // block_k)

    kern = functools.partial(
        _flash_kernel, scale=d ** -0.5, block_q=block_q, block_k=block_k,
        causal=causal, window=window, seq_len=s)

    out, lse = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh_, qi, ki: (bh_, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh_, qi, ki: (bh_, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh_, qi, ki: (bh_, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh_, qi, ki: (bh_, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh_, qi, ki: (bh_, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    out = out.reshape(b, h, s, d)
    if return_lse:
        return out, lse.reshape(b, h, s)
    return out


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_scr,
                         *, scale: float, block_q: int, block_k: int,
                         causal: bool, window: Optional[int], seq_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q = q_ref[0].astype(jnp.float32)                 # (bq, d)
    k = k_ref[0].astype(jnp.float32)                 # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)               # (bq, d)
    lse = lse_ref[0]                                 # (bq, 1)
    delta = delta_ref[0]                             # (bq, 1)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    mask = _block_mask(qi, ki, block_q=block_q, block_k=block_k,
                       causal=causal, window=window, seq_len=seq_len)
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    dq_scr[...] += jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_scr, dv_scr,
                          *, scale: float, block_q: int, block_k: int,
                          causal: bool, window: Optional[int], seq_len: int):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q = q_ref[0].astype(jnp.float32)                 # (bq, d)
    k = k_ref[0].astype(jnp.float32)                 # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)               # (bq, d)
    lse = lse_ref[0]                                 # (bq, 1)
    delta = delta_ref[0]                             # (bq, 1)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    mask = _block_mask(qi, ki, block_q=block_q, block_k=block_k,
                       causal=causal, window=window, seq_len=seq_len)
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)       # (bq, bk)
    # dv = pᵀ @ do: contract the shared q axis (axis 0 of both operands).
    dv_scr[...] += jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    # dk = dsᵀ @ q: again contract axis 0 — no transposes materialized.
    dk_scr[...] += jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_bwd(q: jax.Array, k: jax.Array, v: jax.Array,
                        do: jax.Array, lse: jax.Array, delta: jax.Array,
                        *, causal: bool = True, window: Optional[int] = None,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool = False):
    """Backward pass on (B, H, S, D) operands -> (dq, dk, dv).

    ``lse`` is the forward's (B, H, S) log-sum-exp; ``delta`` is the
    precomputed row dot ``sum(do * o, -1)`` of the same shape. Runs the dq
    kernel (k innermost) and the dk/dv kernel (q innermost) back to back.
    """
    b, h, s, d = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    bh = b * h
    flat = lambda a: a.reshape(bh, s, d)
    qf, kf, vf, dof = flat(q), flat(k), flat(v), flat(do)
    lsef = lse.reshape(bh, s, 1).astype(jnp.float32)
    deltaf = delta.reshape(bh, s, 1).astype(jnp.float32)

    q_spec = pl.BlockSpec((1, block_q, d), lambda bh_, i, j: (bh_, i, 0))
    row_spec = pl.BlockSpec((1, block_q, 1), lambda bh_, i, j: (bh_, i, 0))

    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, scale=d ** -0.5, block_q=block_q,
            block_k=block_k, causal=causal, window=window, seq_len=s),
        grid=(bh, s // block_q, s // block_k),
        in_specs=[
            q_spec,
            pl.BlockSpec((1, block_k, d), lambda bh_, qi, ki: (bh_, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh_, qi, ki: (bh_, ki, 0)),
            q_spec,
            row_spec,
            row_spec,
        ],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, deltaf)

    k_spec = pl.BlockSpec((1, block_k, d), lambda bh_, ki, qi: (bh_, ki, 0))
    qq_spec = pl.BlockSpec((1, block_q, d), lambda bh_, ki, qi: (bh_, qi, 0))
    qrow_spec = pl.BlockSpec((1, block_q, 1), lambda bh_, ki, qi: (bh_, qi, 0))
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, scale=d ** -0.5, block_q=block_q,
            block_k=block_k, causal=causal, window=window, seq_len=s),
        grid=(bh, s // block_k, s // block_q),
        in_specs=[qq_spec, k_spec, k_spec, qq_spec, qrow_spec, qrow_spec],
        out_specs=[k_spec, k_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, deltaf)

    unflat = lambda a: a.reshape(b, h, s, d)
    return unflat(dq), unflat(dk), unflat(dv)
