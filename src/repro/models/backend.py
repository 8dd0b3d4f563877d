"""Compute-backend switch: pure-jnp (default, CPU/compile-safe) vs Pallas
kernels (TPU target; interpret=True runs the kernel bodies on CPU).

    with backend.use_pallas(interpret=True):
        logits = model.forward(params, batch, cfg)

``repro.models.model`` (attention) and ``repro.models.ssm`` (the SSD scan)
consult :func:`current`. Inside ``use_pallas`` these shapes still run the
jnp reference, silently:

* attention (:func:`attention_ok`): a sequence length S that is not a
  multiple of ``min(block_q, S)`` and ``min(block_k, S)``, or a head dim
  other than 64, 80, 128 or 256;
* the SSD scan (:func:`ssd_ok`): an S that is not a multiple of
  ``min(chunk, S)``, or a head count n that is not a multiple of
  ``min(block_h, n)``; also any call that passes or returns a carried
  state (decode).

Outside ``use_pallas`` attention is always the jnp reference, and the SSD
scan follows ``repro.kernels.ssd_scan.ops.default_impl`` (``pallas`` on a
TPU). The FL split models route their kernels without this switch: the
VGG fc layers in ``repro.kernels.fused_linear.ops`` (Pallas on a TPU
whenever every GEMM dim divides its clamped block; otherwise the jnp
reference) and ``SeqSplitModel`` attention through
``repro.kernels.flash_attention.ops.default_impl``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Optional

_state = threading.local()


@dataclasses.dataclass(frozen=True)
class BackendConfig:
    pallas: bool = False
    interpret: bool = False
    block_q: int = 128
    block_k: int = 128
    ssd_block_h: int = 8


def current() -> BackendConfig:
    return getattr(_state, "cfg", BackendConfig())


@contextlib.contextmanager
def use_pallas(interpret: bool = False, **kw):
    prev = current()
    _state.cfg = BackendConfig(pallas=True, interpret=interpret, **kw)
    try:
        yield
    finally:
        _state.cfg = prev


def attention_ok(seq: int, head_dim: int, block_q: int, block_k: int) -> bool:
    return (seq % min(block_q, seq) == 0 and seq % min(block_k, seq) == 0
            and head_dim in (64, 80, 128, 256))


def ssd_ok(seq: int, n_heads: int, chunk: int, block_h: int) -> bool:
    return seq % min(chunk, seq) == 0 and n_heads % min(block_h, n_heads) == 0
