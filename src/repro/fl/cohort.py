"""Batched cohort split-training engine: one XLA program per FL round.

The seed trainer executed the cohort one device at a time — a fresh jitted
``split_sgd_step`` per device per local epoch, retraced for every distinct
partition point ``l`` (a static argnum) and batch shape, with a ``float(loss)``
host sync after every step. This module replaces that with a single fused
program per round:

* per-device parameters are a struct-of-arrays pytree (leading device axis),
* ``jax.vmap`` runs the split forward/backward for the whole cohort at once,
* ``jax.lax.scan`` iterates the K local epochs inside the same program,
* the shop-floor + base-station FedAvg reduction is fused into the end of the
  step, so nothing round-trips to the host until the round result is read.

**Partition point handled as data (masking, not bucketing).** Split training
at partition point ``l`` computes *exactly* the same parameter update as
unsplit SGD — the boundary activation/error exchange is mathematically
transparent (proved by ``tests/test_split_training.py``). The engine
therefore executes the mathematically-equal fused forward/backward once per
device and keeps ``l_n`` a *traced per-device array*: it selects, per device,
which layer boundary's activation statistics are reported (the tensor that
would cross the device→gateway link), via a masked gather over the stacked
per-layer activation norms. The alternative — bucketing devices by ``l`` and
running a separate two-segment program per bucket — would compile
``O(distinct l)`` programs, reintroduce per-bucket host syncs, and change
shapes whenever the scheduler's partition decisions change; masking compiles
exactly once for all rounds, device subsets and partition vectors. The
tradeoff is that per-tier work is not physically separated on one host — the
tier *accounting* (delay/energy) lives in ``repro.core.costmodel``, which is
where the paper keeps it too.

Fixed-shape batching contract: inputs come from
``repro.fl.data.sample_cohort_batch`` — padded slots with a row-validity
mask, all slots present every round, non-participants zero-masked and
zero-weighted — so varying device subsets never retrace. Slots may use
**tiered widths** (``repro.fl.data.CohortLayout``): slot *i* is padded to
roughly the i-th largest global ``d_tilde`` instead of the global maximum,
and the fused program runs one ``vmap`` segment per tier — same single
compile, a fraction of the padded samples. The per-slot helpers here
(`_local_train`, `_boundary_rms`, `_grads_sigma_lips`) are shared with the
`jax.shard_map`-sharded engine in ``repro.fl.shard``, which wraps them in a
mapped body and turns the FedAvg reductions into masked ``psum`` s.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.fl.data import TieredCohortBatch
from repro.fl.data import traced_batch_indices as _traced_indices
from repro.fl.split import flat_params as _flat
from repro.models.split_model import Params, SplitModel

# The traced bodies bump ``repro.obs`` counters (Python side effects run only
# at trace time), so tests can assert "exactly one compile across rounds":
# ``trace.cohort.round``/``trace.cohort.stats`` count per-round program
# traces, ``trace.cohort.train_scan`` traces of the whole-run fused training
# loop (repro.fl.fused_sim). The named scopes ``gather``, ``local_sgd``,
# ``fedavg`` and ``eval`` mark the train program's parts in its HLO metadata
# (and so in a device trace), for the single-host and the sharded engine.


def _unflatten_stacked(flat_nd: jnp.ndarray, like):
    """(N, P) flat rows -> pytree like ``like`` with leading device axis."""
    leaves, treedef = jax.tree.flatten(like)
    out, i = [], 0
    for leaf in leaves:
        sz = leaf.size
        out.append(flat_nd[:, i:i + sz]
                   .reshape((flat_nd.shape[0],) + leaf.shape)
                   .astype(leaf.dtype))
        i += sz
    return jax.tree.unflatten(treedef, out)


def _masked_rms(a: jax.Array, mask: jax.Array) -> jax.Array:
    """RMS over the valid rows of a (B, ...) activation."""
    a2 = a.reshape(a.shape[0], -1).astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(mask), 1.0) * a2.shape[1]
    return jnp.sqrt(jnp.sum(a2 * a2 * mask[:, None]) / denom)


def _boundary_rms(model: SplitModel, params: Params, x, mask, l) -> jax.Array:
    """RMS of the activation crossing the device->gateway boundary at cut
    ``l`` (a traced int: l=0 ships the raw input, l=model.n_blocks ships
    logits — i.e. everything ran device-side)."""
    norms = [_masked_rms(a, mask) for a in model.activations(params, x)]
    return jnp.take(jnp.stack(norms), l)


# ---------------------------------------------------------------------------
# shared per-slot building blocks (single-host cohort AND sharded engine)
# ---------------------------------------------------------------------------


def _maybe_flatten(model: SplitModel, xs: Tuple[jax.Array, ...]):
    """Per-model input prep, once per round (not inside every scanned
    epoch) — e.g. all-fc stacks flatten images, token models pass through."""
    return tuple(model.prepare_inputs(x) for x in xs)


# Scenario.dtype -> the dtype activations/weights are *computed and shipped*
# in. Master parameters, optimizer math and the stats pass stay float32; the
# control plane (DDSRA) stays x64 (see repro.core.ddsra).
COMPUTE_DTYPES = {"f32": None, "bf16": jnp.bfloat16}


def _cast_floats(tree, dtype):
    """Cast floating leaves of a pytree (bf16 storage/HBM traffic; non-float
    leaves untouched). ``dtype=None`` is the identity."""
    if dtype is None:
        return tree
    return jax.tree.map(
        lambda a: a.astype(dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)


def _local_train(model: SplitModel, params: Params, xs, ys, masks,
                 k_iters: int, lr, compute_dtype: str = "f32"):
    """K local SGD epochs for every slot: one ``vmap`` segment per tier
    inside one ``lax.scan`` over the epochs.

    ``xs/ys/masks`` are per-tier tuples (tier k: ``(S_k, W_k, ...)``).
    Returns (per-tier stacked final params, per-tier last-epoch losses) in
    the same tuple-of-tiers form, so callers control whether slots are
    concatenated locally (single host) or reduced via ``psum`` (sharded).

    ``compute_dtype="bf16"`` runs the forward/backward GEMMs in bfloat16
    (mixed precision): master params stay f32, the cast happens *inside* the
    loss closure so ``value_and_grad`` differentiates through it and the
    gradients come back f32 against the f32 masters; the Pallas kernels
    accumulate in f32 VMEM scratch regardless of operand dtype, and the
    logits are promoted to f32 before the cross-entropy reduction.
    """
    cdt = COMPUTE_DTYPES[compute_dtype]

    def dev_step(p, xb, yb, mb):
        def loss_of(pp):
            logits = model.forward(_cast_floats(pp, cdt),
                                   _cast_floats(xb, cdt))
            return model.masked_loss(logits.astype(jnp.float32), yb, mb)
        loss, g = jax.value_and_grad(loss_of)(p)
        new_p = jax.tree.map(lambda w_, g_: w_ - lr * g_, p, g)
        return new_p, loss

    def one_epoch(p_stacks, _):
        outs = [jax.vmap(dev_step)(p, x, y, m)
                for p, x, y, m in zip(p_stacks, xs, ys, masks)]
        return tuple(o[0] for o in outs), tuple(o[1] for o in outs)

    with jax.named_scope("local_sgd"):
        stacked = tuple(
            jax.tree.map(
                lambda p: jnp.broadcast_to(p, (x.shape[0],) + p.shape),
                params)
            for x in xs)
        final, loss_hist = jax.lax.scan(one_epoch, stacked, None,
                                        length=k_iters)
        # last-epoch losses: matching the sequential path's "last
        # split_sgd_step" loss semantics.
        return final, tuple(lh[-1] for lh in loss_hist)


def _boundary_tiers(model: SplitModel, finals, xs, masks, ls):
    """Per-slot boundary-activation RMS, one vmap segment per tier."""
    return tuple(
        jax.vmap(lambda p, xb, mb, l: _boundary_rms(model, p, xb, mb, l))(
            f, x, m, l)
        for f, x, m, l in zip(finals, xs, masks, ls))


def _split_tiers(v, sizes: Tuple[int, ...]):
    """Split a tier-major per-slot vector/matrix into per-tier pieces."""
    out, off = [], 0
    for s in sizes:
        out.append(v[off:off + s])
        off += s
    return tuple(out)


def _concat_tiers(tree_tuple):
    """Concatenate a tuple of pytrees along the leading (slot) axis."""
    if len(tree_tuple) == 1:
        return tree_tuple[0]
    return jax.tree.map(lambda *ls: jnp.concatenate(ls), *tree_tuple)


def _batch_tiers(batch):
    """(xs, ys, masks) per-tier tuples from a CohortBatch or
    TieredCohortBatch — single-width batches become one-tier tuples."""
    tiers = batch.tiers if isinstance(batch, TieredCohortBatch) else (batch,)
    return (tuple(jnp.asarray(t.x) for t in tiers),
            tuple(jnp.asarray(t.y) for t in tiers),
            tuple(jnp.asarray(t.mask) for t in tiers))


# ---------------------------------------------------------------------------
# one FL round: (devices x K local epochs + FedAvg) fused
# ---------------------------------------------------------------------------


def cohort_round_traced(model: SplitModel, params: Params, xs, ys, masks, l_n,
                        weights, gw_onehot, lr, *, k_iters: int,
                        with_boundary: bool,
                        with_gateway_models: bool = False,
                        compute_dtype: str = "f32"):
    """The fused round as a plain traced function: the body behind the
    per-round jit below, *and* the scan step of the whole-run fused
    training loop (:func:`train_scan` / ``repro.fl.fused_sim``) — one
    implementation, two compilation granularities."""
    obs.count("trace.cohort.round")
    xs = _maybe_flatten(model, xs)
    sizes = tuple(x.shape[0] for x in xs)
    final_t, loss_t = _local_train(model, params, xs, ys, masks, k_iters, lr,
                                   compute_dtype)
    with jax.named_scope("fedavg"):
        final = _concat_tiers(final_t)
        dev_losses = jnp.concatenate(loss_t)

        # fused two-tier FedAvg: gateway-level then BS-level weighted
        # averaging telescopes to one weighted average over participating
        # devices.
        w = weights / jnp.maximum(jnp.sum(weights), 1e-12)
        new_global = jax.tree.map(lambda s: jnp.tensordot(w, s, axes=1),
                                  final)

        active = (weights > 0).astype(jnp.float32)
        gw_count = gw_onehot.T @ active                             # (M,)
        gw_loss = (gw_onehot.T @ (dev_losses * active)) \
            / jnp.maximum(gw_count, 1.0)

    if with_boundary:
        boundary = jnp.concatenate(_boundary_tiers(
            model, final_t, xs, masks, _split_tiers(l_n, sizes)))
    else:    # skip the extra forward pass; l_n stays unused data
        boundary = jnp.zeros_like(weights)

    if with_gateway_models:
        # per-gateway shop-floor FedAvg before the global mix: columns of the
        # (N, M) incidence, weighted by d_tilde and normalized per gateway.
        with jax.named_scope("fedavg"):
            gw_w = gw_onehot * weights[:, None]
            gw_w = gw_w / jnp.maximum(
                jnp.sum(gw_w, axis=0, keepdims=True), 1e-12)
            gw_models = jax.tree.map(
                lambda s: jnp.tensordot(gw_w.T, s, axes=1), final)  # (M,...)
    else:
        gw_models = None

    return new_global, gw_loss, gw_count, dev_losses, boundary, gw_models


_cohort_round = functools.partial(
    jax.jit, static_argnames=("model", "k_iters", "with_boundary",
                              "with_gateway_models", "compute_dtype")
)(cohort_round_traced)


def _eval_hits(model: SplitModel, params: Params, x_eval, y_test, ev_t):
    """``lax.cond``-gated in-scan accuracy snapshot: hit count over the
    full (prepared) test set after this round's update, or -1 on rounds
    ``eval_every`` skips. Runs on the f32 master params, so it equals the
    stepwise loop's post-round ``SplitModel.accuracy`` hit count exactly
    (one full-batch forward; chunking does not change integer hits)."""

    def hits(p):
        logits = model.forward(p, x_eval)
        return jnp.sum(jnp.argmax(logits, -1) == y_test).astype(jnp.int32)

    with jax.named_scope("eval"):
        return jax.lax.cond(ev_t, hits, lambda p: jnp.int32(-1), params)


def _gather_tier(x_all, y_all, pool_lens, batch_lens, data_key, t, devs,
                 width: int):
    """One tier's round-``t`` batches gathered in-program from the
    device-resident shard stacks: every slot's rows by the counter-based
    draw (``repro.fl.data.traced_batch_indices``), with its validity mask.
    Empty slots (``dev = -1``) gather device 0's rows under an all-zero
    mask. Shared by both engines' traced train programs."""
    l_max = x_all.shape[1]

    def one(dev):
        d = jnp.maximum(dev, 0)
        idx = _traced_indices(data_key, t, d, pool_lens[d], width, l_max)
        mb = ((jnp.arange(width) < batch_lens[d]) & (dev >= 0)
              ).astype(jnp.float32)
        return x_all[d][idx], y_all[d][idx], mb

    with jax.named_scope("gather"):
        return jax.vmap(one)(devs)


def _commit_round(params, new_global, losses, gw_loss, any_trained, tr_t):
    """The scan's guards after a round's FedAvg: a round nobody trained
    in keeps the old params (the per-round path skips the program, while
    the normalized average would be zeros), and per-gateway losses update
    only where the gateway trained (``sim.losses[m] = gw_loss[m]``)."""
    with jax.named_scope("fedavg"):
        params = jax.tree.map(
            lambda new, old: jnp.where(any_trained, new, old),
            new_global, params)
        return params, jnp.where(tr_t, gw_loss, losses)


@functools.partial(jax.jit,
                   static_argnames=("model", "k_iters", "compute_dtype"))
def train_scan(model: SplitModel, params: Params, losses0, xs, ys, masks, ls, ws,
               gws, trained, lr, eval_mask, x_test, y_test, *, k_iters: int,
               compute_dtype: str = "f32"):
    """The whole training run as ONE program: ``lax.scan`` of the fused
    round over stacked per-round inputs.

    ``xs/ys/masks/ls/ws/gws`` are per-tier tuples with a leading round
    axis — tier k: ``(T, S_k, ...)`` — and ``trained`` is the (T, M) bool
    trained-gateway mask (the same per-tier structure the sharded twin,
    ``repro.fl.shard._train_scan_program``, shards over the mesh). The
    carry is (global params, per-gateway losses); each trip runs
    :func:`cohort_round_traced` on that round's pre-packed batch + decision
    tensors (``repro.fl.fused_sim`` threads them straight from the traced
    DDSRA decide scan). Two guards keep the scan equal to the stepwise
    loop round-for-round:

    * an all-zero-weight round (nobody trained) keeps the old params — the
      per-round path simply skips the program, while the normalized FedAvg
      here would otherwise average into zeros;
    * per-gateway losses update only where ``trained`` is set, mirroring
      ``sim.losses[m] = gw_loss[m]`` for trained gateways only.

    ``eval_mask`` is the (T,) bool ``eval_every`` schedule: marked rounds
    run a ``lax.cond``-gated test-set forward *inside* the scan (see
    :func:`_eval_hits`), restoring mid-run accuracy snapshots without
    leaving the fused program.

    Returns (final params, final losses (M,), per-round loss history
    (T, M) f32, per-round test hits (T,) int32 — -1 where not evaluated).
    One compile per (topology, rounds) shape.
    """
    obs.count("trace.cohort.train_scan")
    x_eval = model.prepare_inputs(x_test)

    def step(carry, x):
        params, losses = carry
        xs_t, ys_t, masks_t, l_t, w_t, gw_t, tr_t, ev_t = x
        w = jnp.concatenate(w_t)
        new_global, gw_loss, _, _, _, _ = cohort_round_traced(
            model, params, xs_t, ys_t, masks_t, jnp.concatenate(l_t), w,
            jnp.concatenate(gw_t), lr, k_iters=k_iters,
            with_boundary=False, compute_dtype=compute_dtype)
        params, losses = _commit_round(params, new_global, losses, gw_loss,
                                       jnp.sum(w) > 0, tr_t)
        hits = _eval_hits(model, params, x_eval, y_test, ev_t)
        return (params, losses), (losses, hits)

    (params, losses), (loss_hist, hits) = jax.lax.scan(
        step, (params, jnp.asarray(losses0, jnp.float32)),
        (xs, ys, masks, ls, ws, gws, trained, eval_mask))
    return params, losses, loss_hist, hits


@functools.partial(jax.jit,
                   static_argnames=("model", "k_iters", "compute_dtype",
                                    "tier_widths"))
def train_scan_traced(model: SplitModel, params: Params, losses0, x_all, y_all,
                      pool_lens, batch_lens, data_key, ts, slot_devs, ls, ws,
                      gws, trained, lr, eval_mask, x_test, y_test, *,
                      k_iters: int, compute_dtype: str = "f32",
                      tier_widths: Tuple[int, ...]):
    """:func:`train_scan` with the data plane moved INSIDE the program.

    Instead of host-packed ``(T, S_k, W_k, ...)`` batch stacks, each round
    gathers its training batches in-scan from the device-resident shard
    stacks (``repro.fl.data.device_resident_stacks``): ``slot_devs`` maps
    every tier-major slot to its device id (-1 = empty), and the
    counter-based draw ``repro.fl.data.traced_batch_indices(data_key, t,
    dev, ...)`` reproduces the host oracle's indices bit-for-bit — so the
    whole run ships only the decision tensors (a few KB/round) to the
    accelerator, not ``T`` copies of padded sample batches.

    Empty slots gather device 0's rows with an all-zero validity mask; the
    masked loss multiplies their (finite) per-row losses by exactly 0.0,
    so the garbage rows contribute the same exact-zero loss and gradients
    as the host plane's zero padding. ``tier_widths`` is static — it fixes
    each tier's gather width ``W_k``.

    Returns the same (params, losses, loss_hist, hits) as
    :func:`train_scan`.
    """
    obs.count("trace.cohort.train_scan")
    x_eval = model.prepare_inputs(x_test)

    def step(carry, x):
        params, losses = carry
        t, sd_t, l_t, w_t, gw_t, tr_t, ev_t = x
        gathered = [_gather_tier(x_all, y_all, pool_lens, batch_lens,
                                 data_key, t, devs, width)
                    for devs, width in zip(sd_t, tier_widths)]
        xs_t = tuple(g[0] for g in gathered)
        ys_t = tuple(g[1] for g in gathered)
        masks_t = tuple(g[2] for g in gathered)
        w = jnp.concatenate(w_t)
        new_global, gw_loss, _, _, _, _ = cohort_round_traced(
            model, params, xs_t, ys_t, masks_t, jnp.concatenate(l_t), w,
            jnp.concatenate(gw_t), lr, k_iters=k_iters,
            with_boundary=False, compute_dtype=compute_dtype)
        params, losses = _commit_round(params, new_global, losses, gw_loss,
                                       jnp.sum(w) > 0, tr_t)
        hits = _eval_hits(model, params, x_eval, y_test, ev_t)
        return (params, losses), (losses, hits)

    (params, losses), (loss_hist, hits) = jax.lax.scan(
        step, (params, jnp.asarray(losses0, jnp.float32)),
        (ts, slot_devs, ls, ws, gws, trained, eval_mask))
    return params, losses, loss_hist, hits


def cohort_round(model: SplitModel, params: Params, batch, l_n, weights, gw_onehot,
                 k_iters: int, lr, with_boundary: bool = True,
                 with_gateway_models: bool = False,
                 compute_dtype: str = "f32") -> Tuple:
    """Run one fused FL round for the whole cohort.

    batch: ``repro.fl.data.CohortBatch`` (single padded width) or
    ``TieredCohortBatch`` (tiered slot widths, one vmap segment per tier).
    The slot axis is either "all devices", "packed slots" or "tier-major
    tiered slots" — the engine is agnostic; l_n / weights / gw_onehot just
    have to use the same indexing (``TieredCohortBatch.slot_of`` maps
    devices to tier-major slots).
    l_n: (S,) int partition point per slot — traced data, never static.
    weights: (S,) FedAvg weights (d_tilde for participants, 0 otherwise).
    gw_onehot: (S, M) slot->gateway incidence.
    with_boundary: also report each slot's boundary-activation RMS at its
    cut l_n (one extra forward pass).
    with_gateway_models: additionally return the per-gateway shop-floor
    FedAvg models (leading gateway axis), before the global mix — the
    intermediate the Fig. 2 divergence experiment measures.
    compute_dtype: "f32" (default) or "bf16" — the mixed-precision data
    plane (see ``_local_train``); master params and every returned tensor
    stay f32 either way.

    Returns (new_global_params, per_gateway_loss (M,), per_gateway_count (M,),
    per_slot_loss (S,), boundary_rms (S,)), plus the gateway models as a
    sixth element when ``with_gateway_models`` is set.
    """
    xs, ys, masks = _batch_tiers(batch)
    out = _cohort_round(model, params, xs, ys, masks,
                        jnp.asarray(l_n, jnp.int32),
                        jnp.asarray(weights, jnp.float32),
                        jnp.asarray(gw_onehot, jnp.float32),
                        jnp.float32(lr), k_iters=k_iters,
                        with_boundary=with_boundary,
                        with_gateway_models=with_gateway_models,
                        compute_dtype=compute_dtype)
    return out if with_gateway_models else out[:5]


def buffer_fedavg(models, weights):
    """Weighted FedAvg over a list of buffered model pytrees.

    The aggregation primitive of the buffered async engine
    (``repro.fl.async_engine``): ``models`` is a list of same-structure
    parameter pytrees (e.g. per-gateway shop-floor models pulled from the
    staleness buffer) and ``weights`` their aggregation coefficients —
    typically surviving-sample counts already discounted by staleness.
    Weights are normalized here, so callers pass raw coefficients. Uses the
    same stacked-tensordot idiom as the fused round's in-program FedAvg:
    with every entry at staleness 0 and the full cohort buffered, this
    reproduces ``_cohort_round``'s two-tier average (the degenerate-parity
    oracle relies on that).
    """
    w = jnp.asarray(np.asarray(weights), jnp.float32)
    w = w / jnp.maximum(jnp.sum(w), 1e-12)
    return jax.tree.map(
        lambda *leaves: jnp.tensordot(w, jnp.stack(leaves), axes=1), *models)


# ---------------------------------------------------------------------------
# per-device gradient statistics (sigma_n, delta_n, L_n) in one program
# ---------------------------------------------------------------------------


def _grads_sigma_lips(model: SplitModel, params: Params, x, y, mask, lr,
                      sigma_samples: int):
    """Per-device flat batch gradients, sigma_n and L_n — everything in the
    stats pass that needs **no** cross-device reduction, so the sharded
    engine can run it on a local slot shard and only ``psum`` the global
    gradient for delta_n. ``x`` must already be through
    ``model.prepare_inputs``. Returns (grads (N, P), sigma (N,), lips (N,))."""

    def batch_grad(p, xb, yb, mb):
        def loss_of(pp):
            return model.masked_loss(model.forward(pp, xb), yb, mb)
        return _flat(jax.grad(loss_of)(p))

    grads = jax.vmap(lambda xb, yb, mb: batch_grad(params, xb, yb, mb))(
        x, y, mask)                                              # (N, P)

    # sigma_n: per-sample gradient spread. vmap-of-vmap over (device, sample);
    # lax.map over the device axis keeps the (S, P) per-sample grad buffer
    # per-device instead of materializing (N, S, P).
    s = min(sigma_samples, x.shape[1])

    def dev_sigma(args):
        xs, ys, ms = args                                        # (S, ...)
        def one(xi, yi):
            def loss_of(pp):
                return model.loss(model.forward(pp, xi[None]), yi[None])
            return _flat(jax.grad(loss_of)(params))
        per = jax.vmap(one)(xs, ys)                              # (S, P)
        cnt = jnp.maximum(jnp.sum(ms), 1.0)
        mean_g = jnp.sum(per * ms[:, None], axis=0) / cnt
        dev = jnp.linalg.norm(per - mean_g[None], axis=1)
        return jnp.sum(dev * ms) / cnt

    sigma = jax.lax.map(dev_sigma, (x[:, :s], y[:, :s], mask[:, :s]))

    # L_n: two-point secant along the SGD direction.
    flat_params = _flat(params)
    pert = _unflatten_stacked(flat_params[None] - lr * grads, params)
    grads2 = jax.vmap(batch_grad)(pert, x, y, mask)
    dw = jnp.linalg.norm(jax.vmap(_flat)(pert) - flat_params[None], axis=1)
    lips = jnp.linalg.norm(grads2 - grads, axis=1) / jnp.maximum(dw, 1e-9)

    return grads, sigma, lips


@functools.partial(jax.jit, static_argnames=("model", "sigma_samples"))
def _cohort_stats(model: SplitModel, params: Params, x, y, mask, mix_weights,
                  lr, *, sigma_samples: int):
    obs.count("trace.cohort.stats")
    x = model.prepare_inputs(x)

    grads, sigma, lips = _grads_sigma_lips(model, params, x, y, mask, lr,
                                           sigma_samples)

    # delta_n: divergence from the D_n-weighted global gradient.
    global_g = jnp.tensordot(mix_weights, grads, axes=1)
    delta = jnp.linalg.norm(grads - global_g[None], axis=1)

    return sigma, delta, lips


def cohort_stats(model: SplitModel, params: Params, batch, mix_weights, lr,
                 sigma_samples: int):
    """sigma/delta/Lipschitz for every device in one jitted program
    (the seed ran O(devices x samples) sequential jit calls)."""
    return _cohort_stats(model, params,
                         jnp.asarray(batch.x), jnp.asarray(batch.y),
                         jnp.asarray(batch.mask),
                         jnp.asarray(mix_weights, jnp.float32),
                         jnp.float32(lr), sigma_samples=sigma_samples)
