"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret=True executes the kernel bodies on CPU)."""
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ops import gqa_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro import obs
from repro.kernels import autotune
from repro.kernels.fused_linear import kernel as fl_kernel
from repro.kernels.fused_linear import ops as fused_ops
from repro.kernels.fused_linear.kernel import (fused_linear,
                                               fused_linear_bwd_dw_db,
                                               fused_linear_bwd_dx,
                                               skinny_plan, tile_plan)
from repro.kernels.fused_linear.ref import (fused_linear_bwd_dw_db_ref,
                                            fused_linear_bwd_dx_ref,
                                            fused_linear_ref)
from repro.kernels.ssd_scan.kernel import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_ref

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (b, h, s, d, causal, window, bq, bk)
    (2, 2, 256, 64, True, None, 128, 128),
    (1, 4, 256, 128, True, None, 64, 64),
    (2, 1, 128, 64, False, None, 64, 128),
    (1, 2, 512, 64, True, 128, 128, 128),
    (1, 1, 128, 128, True, 64, 32, 32),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_matches_ref(case, dtype):
    b, h, s, d, causal, window, bq, bk = case
    keys = jax.random.split(jax.random.PRNGKey(hash(case) % 2**31), 3)
    q, k, v = (_rand(kk, (b, h, s, d), dtype) for kk in keys)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=bq, block_k=bk, interpret=True)
    ref = attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32), causal=causal, window=window)
    np.testing.assert_allclose(out.astype(jnp.float32), ref,
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_gqa_wrapper_matches_grouped_ref():
    b, s, h, kvh, d = 2, 128, 8, 2, 64
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(keys[0], (b, s, h, d), jnp.float32)
    k = _rand(keys[1], (b, s, kvh, d), jnp.float32)
    v = _rand(keys[2], (b, s, kvh, d), jnp.float32)
    out = gqa_attention(q, k, v, interpret=True, use_pallas=True, block_q=64,
                        block_k=64)
    ref = gqa_attention(q, k, v, use_pallas=False)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_matches_model_layer_attention():
    """Kernel agrees with the model's chunked-jnp attention path."""
    from repro.models.layers import causal_attention
    b, s, h, d = 2, 128, 4, 64
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (_rand(kk, (b, s, h, d), jnp.float32) for kk in keys)
    out = gqa_attention(q, k, v, interpret=True, block_q=64, block_k=64)
    ref = causal_attention(q, k, v, block_q=64)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------

SSD_CASES = [
    # (b, s, n, p, ds, chunk, block_h)
    (2, 128, 8, 32, 16, 32, 4),
    (1, 256, 4, 64, 32, 64, 4),
    (1, 64, 2, 16, 8, 64, 2),
    (2, 256, 8, 64, 64, 128, 8),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_scan_matches_sequential_ref(case, dtype):
    b, s, n, p, ds, chunk, bh = case
    keys = jax.random.split(jax.random.PRNGKey(hash(case) % 2**31), 5)
    xh = _rand(keys[0], (b, s, n, p), dtype)
    dt = jax.nn.softplus(_rand(keys[1], (b, s, n), jnp.float32)) * 0.5
    a_log = _rand(keys[2], (n,), jnp.float32) * 0.3
    b_ssm = (_rand(keys[3], (b, s, ds), jnp.float32) * 0.5).astype(dtype)
    c_ssm = (_rand(keys[4], (b, s, ds), jnp.float32) * 0.5).astype(dtype)
    out = ssd_scan(xh, dt, a_log, b_ssm, c_ssm, chunk=chunk, block_h=bh,
                   interpret=True)
    ref = ssd_ref(xh.astype(jnp.float32), dt, a_log,
                  b_ssm.astype(jnp.float32), c_ssm.astype(jnp.float32))
    tol = {jnp.float32: 1e-4, jnp.bfloat16: 5e-2}[dtype]
    np.testing.assert_allclose(out.astype(jnp.float32), ref, atol=tol, rtol=tol)


def test_ssd_op_vjp_matches_ref_grads():
    """The ssd_scan_vjp custom VJP: gradients through the (interpret) kernel
    forward equal gradients through the sequential oracle — the backward is
    a recompute through ssd_ref, so this pins that the residual plumbing and
    the impl dispatch agree."""
    from repro.kernels.ssd_scan.ops import ssd
    b, s, n, p, ds = 1, 64, 4, 16, 8
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    xh = _rand(keys[0], (b, s, n, p), jnp.float32)
    dt = jax.nn.softplus(_rand(keys[1], (b, s, n), jnp.float32)) * 0.5
    a_log = _rand(keys[2], (n,), jnp.float32) * 0.3
    b_ssm = _rand(keys[3], (b, s, ds), jnp.float32) * 0.5
    c_ssm = _rand(keys[4], (b, s, ds), jnp.float32) * 0.5

    def loss_via(impl):
        def f(xh_, bs_, cs_):
            y = ssd(xh_, dt, a_log, bs_, cs_, chunk=32, block_h=4,
                    impl=impl)
            return jnp.sum(y.astype(jnp.float32) ** 2)
        return jax.grad(f, argnums=(0, 1, 2))(xh, b_ssm, c_ssm)

    got = loss_via("interpret")
    want = loss_via("ref")
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-4, rtol=2e-4)


def test_model_chunked_ssd_matches_sequential_ref():
    """The model's own chunked SSD (repro.models.ssm) is also validated."""
    from repro.models.ssm import ssd_chunked
    b, s, n, p, ds = 2, 128, 4, 32, 16
    keys = jax.random.split(jax.random.PRNGKey(2), 5)
    xh = _rand(keys[0], (b, s, n, p), jnp.float32)
    dt = jax.nn.softplus(_rand(keys[1], (b, s, n), jnp.float32)) * 0.5
    a_log = _rand(keys[2], (n,), jnp.float32) * 0.3
    b_ssm = _rand(keys[3], (b, s, ds), jnp.float32) * 0.5
    c_ssm = _rand(keys[4], (b, s, ds), jnp.float32) * 0.5
    y, _ = ssd_chunked(xh, dt, a_log, b_ssm, c_ssm, chunk=32)
    ref = ssd_ref(xh, dt, a_log, b_ssm, c_ssm)
    np.testing.assert_allclose(y, ref, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# fused linear
# ---------------------------------------------------------------------------

LIN_CASES = [
    # (m, k, n, act, bm, bn, bk)
    (128, 128, 128, "relu", 128, 128, 128),
    (256, 512, 128, "silu", 128, 128, 128),
    (64, 256, 512, "none", 64, 128, 64),
    (128, 384, 256, "gelu", 64, 128, 128),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", LIN_CASES)
def test_fused_linear_matches_ref(case, dtype):
    m, k, n, act, bm, bn, bk = case
    keys = jax.random.split(jax.random.PRNGKey(hash(case) % 2**31), 3)
    x = _rand(keys[0], (m, k), dtype)
    w = _rand(keys[1], (k, n), dtype) / np.sqrt(k)
    b = _rand(keys[2], (n,), dtype)
    out = fused_linear(x, w, b, activation=act, block_m=bm, block_n=bn,
                       block_k=bk, interpret=True)
    ref = fused_linear_ref(x.astype(jnp.float32), w.astype(jnp.float32),
                           b.astype(jnp.float32), act)
    np.testing.assert_allclose(out.astype(jnp.float32), ref,
                               atol=TOL[dtype], rtol=TOL[dtype])


# ---------------------------------------------------------------------------
# fused linear backward kernels (interpret mode runs the kernel bodies)
# ---------------------------------------------------------------------------

BWD_CASES = [
    # (m, k, n, mask, bm, bn, bk) — non-square tiles included
    (128, 128, 128, "relu", 128, 128, 128),
    (256, 512, 128, "none", 128, 128, 128),
    (64, 256, 512, "relu", 32, 128, 64),
    (96, 160, 192, "relu", 48, 64, 32),
    (128, 384, 256, "none", 64, 128, 128),
]


def _seed(obj) -> int:
    """Deterministic across processes (str hashes are salted per run)."""
    return zlib.crc32(repr(obj).encode())


def _bwd_operands(case, dtype):
    m, k, n, mask, _, _, _ = case
    keys = jax.random.split(jax.random.PRNGKey(_seed(case)), 3)
    x = _rand(keys[0], (m, k), dtype)
    w = (_rand(keys[1], (k, n), jnp.float32) / np.sqrt(k)).astype(dtype)
    dy = _rand(keys[2], (m, n), dtype)
    y = fused_linear_ref(x, w, jnp.zeros((n,), dtype), "relu") \
        if mask == "relu" else None
    return x, w, dy, y


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", BWD_CASES)
def test_bwd_dx_kernel_matches_ref(case, dtype):
    m, k, n, mask, bm, bn, bk = case
    x, w, dy, y = _bwd_operands(case, dtype)
    out = fused_linear_bwd_dx(dy, w, y, mask=mask, block_m=bm, block_n=bn,
                              block_k=bk, interpret=True)
    ref = fused_linear_bwd_dx_ref(dy.astype(jnp.float32),
                                  w.astype(jnp.float32),
                                  None if y is None else y.astype(jnp.float32),
                                  mask=mask)
    np.testing.assert_allclose(out.astype(jnp.float32), ref,
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", BWD_CASES)
def test_bwd_dw_db_kernel_matches_ref(case, dtype):
    m, k, n, mask, bm, bn, bk = case
    x, w, dy, y = _bwd_operands(case, dtype)
    dw, db = fused_linear_bwd_dw_db(x, dy, y, mask=mask, block_m=bm,
                                    block_n=bn, block_k=bk, interpret=True)
    dw_ref, db_ref = fused_linear_bwd_dw_db_ref(
        x.astype(jnp.float32), dy.astype(jnp.float32),
        None if y is None else y.astype(jnp.float32), mask=mask)
    tol = {jnp.float32: 1e-4, jnp.bfloat16: 1e-1}[dtype]
    np.testing.assert_allclose(dw.astype(jnp.float32), dw_ref,
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(db.astype(jnp.float32), db_ref,
                               atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# the differentiable op: gradients through the Pallas path vs jax.grad(ref)
# ---------------------------------------------------------------------------

GRAD_SHAPES = [(128, 256, 128), (64, 128, 384)]   # tile-aligned, non-square


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("act", ["none", "relu", "silu", "gelu"])
@pytest.mark.parametrize("shape", GRAD_SHAPES)
def test_linear_grad_matches_ref_autodiff(shape, act, dtype):
    """Interpret-mode gradient check: the custom-VJP backward kernels agree
    with jax.grad of the pure-jnp oracle for every activation."""
    m, k, n = shape
    keys = jax.random.split(jax.random.PRNGKey(_seed((shape, act))), 4)
    x = _rand(keys[0], (m, k), dtype)
    w = (_rand(keys[1], (k, n), jnp.float32) / np.sqrt(k)).astype(dtype)
    b = _rand(keys[2], (n,), dtype)
    ct = _rand(keys[3], (m, n), jnp.float32)

    def loss_kernel(x, w, b):
        y = fused_ops.linear(x, w, b, activation=act, impl="interpret")
        return jnp.vdot(y.astype(jnp.float32), ct)

    def loss_ref(x, w, b):
        y = fused_linear_ref(x.astype(jnp.float32), w.astype(jnp.float32),
                             b.astype(jnp.float32), act)
        return jnp.vdot(y, ct)

    got = jax.grad(loss_kernel, argnums=(0, 1, 2))(x, w, b)
    want = jax.grad(loss_ref, argnums=(0, 1, 2))(x, w, b)
    tol = {jnp.float32: 1e-4, jnp.bfloat16: 1e-2}[dtype]
    for g, r, name in zip(got, want, ("dx", "dw", "db")):
        g, r = g.astype(jnp.float32), r.astype(jnp.float32)
        # bf16 storage quantizes large-scale grads to ~1e-2 relative either
        # way, so its atol scales with the gradient's own magnitude; f32
        # holds the strict 1e-4.
        scale = 1.0 if dtype == jnp.float32 \
            else max(1.0, float(jnp.max(jnp.abs(r))))
        np.testing.assert_allclose(g, r, atol=tol * scale, rtol=tol,
                                   err_msg=name)


@pytest.mark.parametrize("act", ["none", "relu", "gelu"])
def test_linear_backward_contains_no_transpose(act):
    """The training-path jaxpr of the Pallas/interpret impl must carry the
    operand transposes in BlockSpec index maps / dot_general dimension
    numbers only — no transpose primitive on w or x anywhere."""
    x = jnp.ones((128, 256))
    w = jnp.ones((256, 128))
    b = jnp.ones((128,))

    def loss(x, w, b):
        return fused_ops.linear(x, w, b, activation=act,
                                impl="interpret").sum()

    jaxpr = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, w, b))
    assert "transpose" not in jaxpr
    # the ref fallback keeps the same property (dot_general dim numbers)
    def loss_ref(x, w, b):
        return fused_ops.linear(x, w, b, activation=act, impl="ref").sum()
    assert "transpose" not in str(
        jax.make_jaxpr(jax.grad(loss_ref, argnums=(0, 1, 2)))(x, w, b))


# ---------------------------------------------------------------------------
# tile_plan: the one shared clamping/alignment rule + the routing boundary
# ---------------------------------------------------------------------------

def test_tile_plan_clamps_per_dim_and_gates_kernels():
    plan = tile_plan(100, 300, 128)
    assert (plan.block_m, plan.block_k, plan.block_n) == (100, 128, 128)
    assert not plan.aligned            # 300 % 128 != 0
    assert tile_plan(100, 256, 128).aligned     # 100 clamps to one block
    assert tile_plan(127, 127, 127).aligned     # single full-size block
    assert not tile_plan(129, 128, 128).aligned


# ---------------------------------------------------------------------------
# skinny_plan: the shape-derived tiling of a slot's GEMMs
# ---------------------------------------------------------------------------

@pytest.fixture()
def no_table(tmp_path, monkeypatch):
    """An empty kernel-selection table, so ``_plan`` falls to its rules."""
    monkeypatch.setenv("REPRO_AUTOTUNE_DIR", str(tmp_path))
    autotune.clear_cache()
    yield
    autotune.clear_cache()


def _plan_counts() -> dict:
    return {src: obs.counters[f"fused_linear.plan.{src}"]
            for src in ("table", "skinny", "default")}


def _vmem_limits(m, k, n, plan, itemsize=4) -> dict:
    """The VMEM limit each kernel passes to Mosaic under ``plan`` (None:
    the compiler's default scoped limit), read from the traced call."""
    dt = {4: jnp.float32, 2: jnp.bfloat16}[itemsize]
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, dt)   # noqa: E731
    kw = dict(block_m=plan.block_m, block_k=plan.block_k,
              block_n=plan.block_n, interpret=True)
    calls = {
        "fwd": (lambda x, w, b: fused_linear(x, w, b, **kw),
                (sds(m, k), sds(k, n), sds(n))),
        "dx": (lambda dy, w, y: fused_linear_bwd_dx(dy, w, y, mask="relu",
                                                    **kw),
               (sds(m, n), sds(k, n), sds(m, n))),
        "dw_db": (lambda x, dy, y: fused_linear_bwd_dw_db(x, dy, y,
                                                          mask="relu", **kw),
                  (sds(m, k), sds(m, n), sds(m, n))),
    }
    out = {}
    for kern, (fn, args) in calls.items():
        (eqn,) = [e for e in jax.make_jaxpr(fn)(*args).eqns
                  if e.primitive.name == "pallas_call"]
        params = eqn.params["compiler_params"]
        out[kern] = params["mosaic_tpu"].vmem_limit_bytes if params else None
    return out


# (m, k, n): VGG-11's fc layers at the slot width 95, one row, and a row
# count above SKINNY_M_MAX
SKINNY_SHAPES = [(95, 512, 4096), (95, 4096, 4096), (95, 4096, 10),
                 (1, 4096, 4096), (300, 4096, 4096)]


@pytest.mark.parametrize("shape", SKINNY_SHAPES,
                         ids=lambda s: "x".join(map(str, s)))
def test_skinny_plan_rule(no_table, shape):
    m, k, n = shape
    before = tile_plan(m, k, n)
    plan = fused_ops._plan(m, k, n, np.dtype(np.float32), "pallas")
    assert plan.aligned == before.aligned            # same routing verdict
    if m > fl_kernel.SKINNY_M_MAX:
        assert skinny_plan(m, k, n, 4) is None
        assert plan == before                        # today's plan, exactly
        return
    assert plan == skinny_plan(m, k, n, 4)
    assert plan.block_m == m
    for dim, blk in ((k, plan.block_k), (n, plan.block_n)):
        assert dim % blk == 0 and (blk % 128 == 0 or blk == dim)
    # the whole of K first: the row operand is one block, read once
    assert plan.block_k == k
    # far fewer grid steps than the clamped-128 plan's
    steps = (k // plan.block_k) * (n // plan.block_n)
    assert steps <= 16 < (k // before.block_k) * (n // before.block_n) \
        or n <= 128
    limits = _vmem_limits(m, k, n, plan)
    for kern in fl_kernel.KERNELS:
        need = fl_kernel.vmem_bytes(kern, m, plan.block_k, plan.block_n, 4)
        assert need <= fl_kernel.SKINNY_VMEM_BUDGET
        limit = limits[kern] or fl_kernel._SCOPED_VMEM_DEFAULT
        assert need <= limit, (kern, need, limit)


@pytest.mark.parametrize("shape", SKINNY_SHAPES + [
    (1000, 4096, 4096),          # the 1,000-row eval forward: ref
    (127, 128, 129), (129, 128, 128), (96, 300, 128), (8, 200, 64)],
    ids=lambda s: "x".join(map(str, s)))
def test_routing_verdict_unchanged_by_skinny_plan(no_table, shape):
    """Whether a shape runs the kernels or the ref is the clamped-128
    plan's verdict, whichever plan then tiles it."""
    m, k, n = shape
    for dtype in (np.float32, jnp.bfloat16):
        plan = fused_ops._plan(m, k, n, np.dtype(dtype), "pallas")
        assert plan.aligned == tile_plan(m, k, n).aligned


def test_plan_counter_once_per_traced_call_site(no_table):
    """Forward, dx and dw+db each count their plan's source once per trace;
    the ref path counts nothing."""
    def grad_of(m, k, n, impl):
        x, w, b = jnp.ones((m, k)), jnp.ones((k, n)), jnp.ones((n,))
        loss = lambda x, w, b: fused_ops.linear(   # noqa: E731
            x, w, b, activation="relu", impl=impl).sum()
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, w, b)

    def delta(*args):
        before = _plan_counts()
        grad_of(*args)
        return {s: _plan_counts()[s] - before[s] for s in before}

    assert delta(24, 512, 384, "interpret") == \
        {"table": 0, "skinny": 3, "default": 0}
    assert delta(384, 128, 128, "interpret") == \
        {"table": 0, "skinny": 0, "default": 3}
    assert delta(24, 512, 384, "ref") == \
        {"table": 0, "skinny": 0, "default": 0}
    backend = autotune.backend_id(interpret=True)
    autotune.record("fused_linear", (24, 512, 384), "float32", backend,
                    (24, 128, 128), us=1.0, baseline_us=2.0, save=False)
    assert delta(24, 512, 384, "interpret") == \
        {"table": 3, "skinny": 0, "default": 0}


def _budget_for(m, k, n, bk, bn) -> int:
    """The VMEM budget under which ``skinny_plan`` picks (bk, bn)."""
    return max(fl_kernel.vmem_bytes(kern, m, bk, bn, 4)
               for kern in fl_kernel.KERNELS)


# (bk, bn) the rule is steered to at (24, 512, 384): one block, three N
# blocks with x resident, and K split too (the row operand re-fetched)
SKINNY_BLOCKS = [(512, 384), (512, 128), (128, 128)]


@pytest.mark.parametrize("act", ["relu", "none"])
@pytest.mark.parametrize("blocks", SKINNY_BLOCKS,
                         ids=lambda b: "x".join(map(str, b)))
def test_skinny_plan_vmapped_kernels_match_ref(blocks, act):
    """Forward, dx and dw+db under the skinny rule's blocks, vmapped over
    slots as split training runs them, against the references."""
    slots, (m, k, n) = 3, (24, 512, 384)
    plan = skinny_plan(m, k, n, 4, budget=_budget_for(m, k, n, *blocks))
    assert (plan.block_m, plan.block_k, plan.block_n) == (m, *blocks)
    kw = dict(block_m=plan.block_m, block_k=plan.block_k,
              block_n=plan.block_n, interpret=True)
    mask = "relu" if act == "relu" else "none"
    keys = jax.random.split(jax.random.PRNGKey(_seed((blocks, act))), 4)
    x = _rand(keys[0], (slots, m, k), jnp.float32)
    w = _rand(keys[1], (slots, k, n), jnp.float32) / np.sqrt(k)
    b = _rand(keys[2], (slots, n), jnp.float32)
    dy = _rand(keys[3], (slots, m, n), jnp.float32)

    y = jax.vmap(lambda x, w, b: fused_linear(x, w, b, activation=act,
                                              **kw))(x, w, b)
    y_ref = jax.vmap(lambda x, w, b: fused_linear_ref(x, w, b, act))(x, w, b)
    np.testing.assert_allclose(y, y_ref, atol=TOL[jnp.float32],
                               rtol=TOL[jnp.float32])
    yy = y_ref if mask == "relu" else None
    dx = jax.vmap(lambda d, w, y: fused_linear_bwd_dx(d, w, y, mask=mask,
                                                      **kw),
                  in_axes=(0, 0, None if yy is None else 0))(dy, w, yy)
    dx_ref = jax.vmap(lambda d, w, y: fused_linear_bwd_dx_ref(d, w, y,
                                                              mask=mask),
                      in_axes=(0, 0, None if yy is None else 0))(dy, w, yy)
    np.testing.assert_allclose(dx, dx_ref, atol=1e-4, rtol=1e-4)
    dw, db = jax.vmap(
        lambda x, d, y: fused_linear_bwd_dw_db(x, d, y, mask=mask, **kw),
        in_axes=(0, 0, None if yy is None else 0))(x, dy, yy)
    dw_ref, db_ref = jax.vmap(
        lambda x, d, y: fused_linear_bwd_dw_db_ref(x, d, y, mask=mask),
        in_axes=(0, 0, None if yy is None else 0))(x, dy, yy)
    np.testing.assert_allclose(dw, dw_ref, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(db, db_ref, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("act", ["relu", "none", "gelu"])
def test_skinny_plan_vmapped_grad_matches_ref(no_table, act):
    """jax.grad of the op, vmapped over slots, tiled by the skinny rule
    (which the plan counter shows), against jax.grad of the reference."""
    slots, (m, k, n) = 3, (24, 512, 384)
    keys = jax.random.split(jax.random.PRNGKey(_seed(("grad", act))), 4)
    x = _rand(keys[0], (slots, m, k), jnp.float32)
    w = _rand(keys[1], (slots, k, n), jnp.float32) / np.sqrt(k)
    b = _rand(keys[2], (slots, n), jnp.float32)
    ct = _rand(keys[3], (slots, m, n), jnp.float32)

    def slot_grads(linear):
        def loss(x, w, b, ct):
            return jnp.vdot(linear(x, w, b), ct)
        return jax.vmap(jax.grad(loss, argnums=(0, 1, 2)))(x, w, b, ct)

    before = _plan_counts()["skinny"]
    got = slot_grads(lambda x, w, b: fused_ops.linear(
        x, w, b, activation=act, impl="interpret"))
    # forward, dx, dw+db; a smooth activation's backward adds the remat GEMM
    assert _plan_counts()["skinny"] - before == (3 if act != "gelu" else 4)
    want = slot_grads(lambda x, w, b: fused_linear_ref(x, w, b, act))
    for g, r, name in zip(got, want, ("dx", "dw", "db")):
        np.testing.assert_allclose(g, r, atol=1e-4, rtol=1e-4, err_msg=name)


OFF_TILE = [127, 128, 129]


@pytest.mark.parametrize("act", ["relu", "silu"])
@pytest.mark.parametrize("m", OFF_TILE)
@pytest.mark.parametrize("k", OFF_TILE)
@pytest.mark.parametrize("n", OFF_TILE)
def test_routing_boundary_off_tile_shapes(m, k, n, act):
    """Property: whichever side of the pallas↔ref boundary tile_plan routes
    to, forward and all three backward contractions are correct — the exact
    shapes (127/129) that straddle the 128-tile alignment rule."""
    keys = jax.random.split(jax.random.PRNGKey(m * 10007 + k * 101 + n), 3)
    x = _rand(keys[0], (m, k), jnp.float32)
    w = _rand(keys[1], (k, n), jnp.float32) / np.sqrt(k)
    b = _rand(keys[2], (n,), jnp.float32)

    def loss_kernel(x, w, b):
        return fused_ops.linear(x, w, b, activation=act,
                                impl="interpret").sum()

    def loss_ref(x, w, b):
        return fused_linear_ref(x, w, b, act).sum()

    np.testing.assert_allclose(
        fused_ops.linear(x, w, b, activation=act, impl="interpret"),
        fused_linear_ref(x, w, b, act), atol=1e-4, rtol=1e-4)
    got = jax.grad(loss_kernel, argnums=(0, 1, 2))(x, w, b)
    want = jax.grad(loss_ref, argnums=(0, 1, 2))(x, w, b)
    for g, r, name in zip(got, want, ("dx", "dw", "db")):
        np.testing.assert_allclose(g, r, atol=1e-4, rtol=1e-4, err_msg=name)


@pytest.mark.parametrize("act", ["none", "relu", "silu", "gelu"])
@pytest.mark.parametrize("m,k,n", [(127, 128, 129), (129, 127, 128),
                                   (128, 256, 128)])
def test_routing_boundary_off_tile_bf16(m, k, n, act):
    """bf16 mixed-precision parity on the exact shapes straddling the
    128-tile routing boundary: forward and all three backward contractions
    of the bf16 path (f32 VMEM accumulation) match the f32 reference at
    bf16 storage tolerances, on both sides of the pallas↔ref boundary."""
    keys = jax.random.split(jax.random.PRNGKey(m * 211 + k * 31 + n), 3)
    x16 = _rand(keys[0], (m, k), jnp.bfloat16)
    w16 = (_rand(keys[1], (k, n), jnp.float32) / np.sqrt(k)
           ).astype(jnp.bfloat16)
    b16 = _rand(keys[2], (n,), jnp.bfloat16)
    x32, w32, b32 = (a.astype(jnp.float32) for a in (x16, w16, b16))

    def loss_kernel(x, w, b):
        return fused_ops.linear(x, w, b, activation=act,
                                impl="interpret").astype(jnp.float32).sum()

    def loss_ref(x, w, b):
        return fused_linear_ref(x, w, b, act).sum()

    tol = TOL[jnp.bfloat16]
    np.testing.assert_allclose(
        np.asarray(fused_ops.linear(x16, w16, b16, activation=act,
                                    impl="interpret"), jnp.float32),
        fused_linear_ref(x32, w32, b32, act), atol=tol, rtol=tol)
    got = jax.grad(loss_kernel, argnums=(0, 1, 2))(x16, w16, b16)
    want = jax.grad(loss_ref, argnums=(0, 1, 2))(x32, w32, b32)
    for g, r, name in zip(got, want, ("dx", "dw", "db")):
        assert g.dtype == jnp.bfloat16    # cotangents match operand storage
        g, r = np.asarray(g, jnp.float32), np.asarray(r)
        scale = max(1.0, float(np.max(np.abs(r))))
        np.testing.assert_allclose(g, r, atol=tol * scale, rtol=tol,
                                   err_msg=name)


def test_linear_bf16_e2e_no_transpose_pinned():
    """Pinned acceptance test for the mixed-precision data plane: the bf16
    fused_linear fwd+bwd passes parity vs the f32 reference at bf16
    tolerances AND its whole training-step jaxpr carries zero transpose
    primitives (operand transposition lives in BlockSpec index maps /
    dot_general dimension numbers only)."""
    m, k, n = 128, 256, 128
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    x = _rand(keys[0], (m, k), jnp.bfloat16)
    w = (_rand(keys[1], (k, n), jnp.float32) / np.sqrt(k)
         ).astype(jnp.bfloat16)
    b = _rand(keys[2], (n,), jnp.bfloat16)

    def loss(x, w, b):
        return fused_ops.linear(x, w, b, activation="relu",
                                impl="interpret").astype(jnp.float32).sum()

    jaxpr = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, w, b))
    assert "transpose" not in jaxpr

    got = jax.grad(loss, argnums=(0, 1, 2))(x, w, b)
    want = jax.grad(
        lambda x_, w_, b_: fused_linear_ref(x_, w_, b_, "relu").sum(),
        argnums=(0, 1, 2))(*(a.astype(jnp.float32) for a in (x, w, b)))
    for g, r, name in zip(got, want, ("dx", "dw", "db")):
        g, r = np.asarray(g, jnp.float32), np.asarray(r)
        scale = max(1.0, float(np.max(np.abs(r))))
        np.testing.assert_allclose(g, r, atol=2e-2 * scale, rtol=2e-2,
                                   err_msg=name)
