"""Compile the Pallas kernels for a described TPU v5e chip, with no chip
attached: what the interpreter accepts, Mosaic may still refuse (a block
off the (8, 128) tiling, a layout it cannot match), and only a compile for
the real target shows it.

Every test compiles for one chip of a ``v5e:2x2`` topology description and
asserts that the kernel is in the compiled program (``tpu_custom_call``).
The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler library, and a test worker that
imports this file must not take it. Keep these tests in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.fused_linear import kernel as fl_kernel
from repro.kernels.fused_linear import ops as fl_ops
from repro.kernels.ssd_scan import kernel as ssd_kernel

# VGG-11 at width_mult=1.0 on 32x32 inputs: fc1 512->4096, fc2 4096->4096,
# fc3 4096->10, at 128 rows per slot batch.
VGG_FC = [(128, 512, 4096), (128, 4096, 4096), (128, 4096, 10)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache without the chip; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, *specs) -> str:
    return jax.jit(fn).lower(*specs).compile().as_text()


def _spec(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _assert_kernel(text: str, at_least: int = 1):
    n = text.count('custom_call_target="tpu_custom_call"')
    assert n >= at_least, f"{n} tpu_custom_call ops in the compiled program"


@pytest.mark.parametrize("mkn", VGG_FC, ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("which", ["fwd", "dx", "dw_db"])
def test_fused_linear_compiles_for_v5e(one_chip, no_compile_cache, which,
                                       mkn):
    m, k, n = mkn
    s = lambda *shape: _spec(one_chip, shape)
    if which == "fwd":
        text = _compiled_text(
            lambda x, w, b: fl_kernel.fused_linear(x, w, b,
                                                   activation="relu"),
            s(m, k), s(k, n), s(n))
    elif which == "dx":
        text = _compiled_text(
            lambda dy, w, y: fl_kernel.fused_linear_bwd_dx(dy, w, y,
                                                           mask="relu"),
            s(m, n), s(k, n), s(m, n))
    else:
        text = _compiled_text(
            lambda x, dy, y: fl_kernel.fused_linear_bwd_dw_db(x, dy, y,
                                                              mask="relu"),
            s(m, k), s(m, n), s(m, n))
    _assert_kernel(text)


def test_flash_attention_fwd_bwd_compiles_for_v5e(one_chip, no_compile_cache):
    shape = (2, 8, 512, 128)

    def loss_grad(q, k, v):
        def loss(q, k, v):
            return flash_ops.flash_attention(True, None, 128, 128, "pallas",
                                             q, k, v).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    text = _compiled_text(loss_grad, *(_spec(one_chip, shape),) * 3)
    _assert_kernel(text, at_least=3)       # forward, dq, dk/dv


def test_ssd_scan_compiles_for_v5e(one_chip, no_compile_cache):
    # Mamba-2-shaped: 32 heads of 64, d_state 128, 512 tokens
    b, s, n, p, ds = 2, 512, 32, 64, 128
    text = _compiled_text(
        lambda *a: ssd_kernel.ssd_scan(*a, chunk=128, block_h=8),
        _spec(one_chip, (b, s, n, p)), _spec(one_chip, (b, s, n)),
        _spec(one_chip, (n,)), _spec(one_chip, (b, s, ds)),
        _spec(one_chip, (b, s, ds)))
    _assert_kernel(text)


def test_vmapped_linear_grad_compiles_for_v5e(one_chip, no_compile_cache):
    """The split-training shape of the fc layer: a jax.grad of the custom-VJP
    op, vmapped over 12 device slots, with impl="pallas" forced (the
    default routing would pick the jnp reference on this CPU host)."""
    slots, (m, k, n) = 12, VGG_FC[0]

    def slot_grads(x, w, b):
        def loss(x, w, b):
            return fl_ops.linear(x, w, b, activation="relu",
                                 impl="pallas").sum()
        return jax.vmap(jax.grad(loss, argnums=(0, 1, 2)))(x, w, b)

    text = _compiled_text(slot_grads, _spec(one_chip, (slots, m, k)),
                          _spec(one_chip, (slots, k, n)),
                          _spec(one_chip, (slots, n)))
    _assert_kernel(text, at_least=3)       # forward, dx, dw+db
