"""Mamba-2 SSD chunked scan as a Pallas TPU kernel.

Grid: (batch, head_blocks, num_chunks) — chunks innermost and sequential on
TPU, so the inter-chunk SSM state lives in VMEM scratch across chunk steps
(same carry pattern as the flash-attention accumulators). Within a chunk the
dual quadratic form runs on the MXU; the state update is a rank-Q
outer-product accumulation.

Operand layout: the wrapper hands the kernel head-major views — ``xh`` as
(B, n, S, p), ``dt`` as (B, n, S, 1) columns and ``a_log`` as (n, 1, 1) — so
the head block is a leading (untiled) dim of every block and only the chunk
length sits on a tiled axis: each block's last two dims then meet the TPU's
(8, 128) rule for any ``block_h``. Inside a step the heads are an unrolled
loop of 2-D work; the in-chunk prefix sums and the column-to-row flips of
``dt`` are masked reductions over the (Q, Q) causal/diagonal masks, which
lower on the TPU without transposes.

VMEM working set per step: O(Q^2 + block_h * (Q * p + ds * p)) — chosen so
Q=chunk=128..256, block_h<=8 fits comfortably in 16 MB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, alog_ref, b_ref, c_ref, y_ref, h_scr,
                *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    b = b_ref[0].astype(jnp.float32)          # (Q, ds)
    c = c_ref[0].astype(jnp.float32)          # (Q, ds)
    # scores[q, k] = c_q . b_k, shared by every head of the block
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (Q, K)
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = row >= col
    diag = row == col

    def to_row(v_col):                        # (Q, 1) -> (1, Q)
        return jnp.sum(jnp.where(diag, v_col, 0.0), axis=0, keepdims=True)

    for j in range(x_ref.shape[1]):
        x = x_ref[0, j].astype(jnp.float32)   # (Q, p)
        dt_col = dt_ref[0, j].astype(jnp.float32)            # (Q, 1)
        a = -jnp.exp(alog_ref[j].astype(jnp.float32))       # (1, 1)
        adt_col = dt_col * a                  # log-decays
        adt_row = to_row(adt_col)
        # inclusive prefix sums, in both orientations
        cum_col = jnp.sum(jnp.where(causal, adt_row, 0.0), axis=1,
                          keepdims=True)                      # (Q, 1)
        cum_row = jnp.sum(jnp.where(row <= col, adt_col, 0.0), axis=0,
                          keepdims=True)                      # (1, Q)

        # --- intra-chunk dual form ---------------------------------------
        ldec = jnp.where(causal, jnp.exp(cum_col - cum_row), 0.0)
        w = scores * ldec * to_row(dt_col)    # * dt_k
        y_intra = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)

        # --- inter-chunk contribution from carried state -----------------
        h = h_scr[j]                          # (ds, p)
        y_inter = jax.lax.dot_general(
            c, h, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * jnp.exp(cum_col)
        y_ref[0, j] = (y_intra + y_inter).astype(y_ref.dtype)

        # --- state update: S[s, p] = sum_k b[k, s] wk[k] x[k, p] ---------
        cum_last = cum_col[chunk - 1:chunk, :]                # (1, 1)
        wk = jnp.exp(cum_last - cum_col) * dt_col             # (Q, 1)
        s_new = jax.lax.dot_general(b, x * wk, (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        h_scr[j] = h * jnp.exp(cum_last) + s_new


def ssd_scan(xh: jax.Array, dt: jax.Array, a_log: jax.Array,
             b_ssm: jax.Array, c_ssm: jax.Array,
             *, chunk: int = 128, block_h: int = 8,
             interpret: bool = False) -> jax.Array:
    """xh (B,S,n,p); dt (B,S,n); a_log (n,); b/c (B,S,ds) -> (B,S,n,p)."""
    bsz, s, n, p = xh.shape
    ds = b_ssm.shape[-1]
    chunk = min(chunk, s)
    block_h = min(block_h, n)
    assert s % chunk == 0 and n % block_h == 0, (s, chunk, n, block_h)
    grid = (bsz, n // block_h, s // chunk)

    kern = functools.partial(_ssd_kernel, chunk=chunk)
    head_block = pl.BlockSpec((1, block_h, chunk, p),
                              lambda b_, h_, c_: (b_, h_, c_, 0))
    y = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            head_block,
            pl.BlockSpec((1, block_h, chunk, 1),
                         lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((block_h, 1, 1), lambda b_, h_, c_: (h_, 0, 0)),
            pl.BlockSpec((1, chunk, ds), lambda b_, h_, c_: (b_, c_, 0)),
            pl.BlockSpec((1, chunk, ds), lambda b_, h_, c_: (b_, c_, 0)),
        ],
        out_specs=head_block,
        out_shape=jax.ShapeDtypeStruct((bsz, n, s, p), xh.dtype),
        scratch_shapes=[pltpu.VMEM((block_h, ds, p), jnp.float32)],
        interpret=interpret,
    )(xh.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1)[..., None],
      a_log.reshape(n, 1, 1), b_ssm, c_ssm)
    return y.transpose(0, 2, 1, 3)
