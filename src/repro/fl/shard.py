"""Sharded 100+-device cohort engine: ``jax.shard_map`` over the slot axis.

The fused cohort engine (``repro.fl.cohort``) compiles one XLA program per
FL round, but executes the whole packed slot axis on a single accelerator —
fine for the paper's 12-device topology, a ceiling for the 100+-device
cohorts resource-constrained FL deployments target. This module removes
that ceiling by mapping the *same* fused round body over a 1-D ``"cohort"``
device mesh (``repro.sharding.cohort_mesh``):

* **device slots are sharded** — every tier's ``(S_k, W_k, ...)`` batch
  arrays split their slot axis evenly across mesh devices (the
  ``CohortLayout`` rounds each tier's slot count up to a mesh multiple);
* **model parameters are replicated** — each mesh device broadcasts the
  global model to its local slots and trains them exactly as the
  single-host engine would (same ``_local_train`` code);
* **two-tier FedAvg = masked ``psum`` s inside the mapped body** — each
  device reduces its local slots to weighted partial sums, one
  ``psum`` over the ``"cohort"`` axis completes the gateway-level and
  BS-level averages, so the per-gateway shop-floor models *and* the global
  model come out of the same program with no host round-trip.

The stats pass (``repro.fl.cohort.cohort_stats``) shards the same way: only
the global mixed gradient (for delta_n) needs a ``psum``; sigma_n and L_n
are per-device and run on the local shard.

Numerically the sharded round equals the single-host cohort round up to
reduction order (parity pinned at atol 1e-5 in ``tests/test_shard.py``,
including on a forced 8-device CPU mesh via
``XLA_FLAGS=--xla_force_host_platform_device_count=8``). On a 1-device
mesh — the default on a single-device host — it runs as a plain fused
program.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro import obs
from repro.fl import cohort as cohort_lib
from repro.fl import sim as sim_lib
from repro.models.split_model import Params, SplitModel
from repro.sharding import (COHORT_AXIS, REPLICATED, SLOT_SPEC,
                            STACKED_SLOT_SPEC, cohort_mesh)

def _replicated(mesh, tree):
    """Place ``tree`` replicated on ``mesh``. A round's outputs come back
    typed on the mesh; committing the inputs the same way keeps every call
    of a program on one cache entry instead of retracing on round 2."""
    return jax.device_put(tree, NamedSharding(mesh, REPLICATED))


def _psum(v):
    return jax.lax.psum(v, COHORT_AXIS)


def _fedavg_psum(final, w, losses, gw):
    """The two-tier FedAvg + per-gateway loss reduction as masked psums
    over the cohort axis — the reduction core shared by the per-round
    sharded program and the whole-run fused loop. ``final``/``w``/
    ``losses``/``gw`` are local-shard slot-major values; returns the
    replicated (new_global, gw_loss, gw_count, w_sum), under the
    ``fedavg`` named scope."""
    with jax.named_scope("fedavg"):
        w_sum = _psum(jnp.sum(w))
        new_global = jax.tree.map(
            lambda s: _psum(jnp.tensordot(w, s, axes=1))
            / jnp.maximum(w_sum, 1e-12), final)
        active = (w > 0).astype(jnp.float32)
        gw_count = _psum(gw.T @ active)                             # (M,)
        gw_loss = _psum(gw.T @ (losses * active)) \
            / jnp.maximum(gw_count, 1.0)
        return new_global, gw_loss, gw_count, w_sum


@functools.lru_cache(maxsize=None)
def _round_program(mesh, model: SplitModel, k_iters: int, n_tiers: int,
                   with_boundary: bool, with_gateway_models: bool,
                   compute_dtype: str = "f32"):
    """Compile-once sharded round: slots tiled over the mesh, params
    replicated, FedAvg as masked psums inside the mapped body.
    ``compute_dtype`` selects the mixed-precision data plane (part of the
    lru_cache key, so f32 and bf16 rounds compile separate programs)."""

    def body(params, xs, ys, masks, ls, ws, gws, lr):
        obs.count("trace.shard.round")
        xs = cohort_lib._maybe_flatten(model, xs)
        final_t, loss_t = cohort_lib._local_train(
            model, params, xs, ys, masks, k_iters, lr, compute_dtype)
        final = cohort_lib._concat_tiers(final_t)       # local slots only
        w = jnp.concatenate(ws)
        losses = jnp.concatenate(loss_t)
        gw = jnp.concatenate(gws)

        # BS-level FedAvg: local weighted partial sums -> one psum. The
        # gateway-level + BS-level averaging telescopes to a single weighted
        # average over participating slots, as in the single-host engine.
        # Per-gateway losses: masked psums over the slot->gateway incidence.
        new_global, gw_loss, gw_count, _ = _fedavg_psum(final, w, losses, gw)

        if with_boundary:
            boundary = cohort_lib._boundary_tiers(model, final_t, xs, masks, ls)
        else:
            boundary = tuple(jnp.zeros_like(wt) for wt in ws)

        if with_gateway_models:
            # gateway-level (shop-floor) FedAvg before the global mix, also
            # as masked psums: numerator and denominator per gateway column.
            gw_w = gw * w[:, None]                                  # (s, M)
            den = _psum(jnp.sum(gw_w, axis=0))                      # (M,)

            def col_avg(s):
                num = _psum(jnp.tensordot(gw_w.T, s, axes=1))       # (M, ...)
                return num / jnp.maximum(den, 1e-12).reshape(
                    (-1,) + (1,) * (num.ndim - 1))

            gw_models = jax.tree.map(col_avg, final)
        else:
            gw_models = None

        return new_global, gw_loss, gw_count, loss_t, boundary, gw_models

    tile, rep = SLOT_SPEC, REPLICATED
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(rep, tile, tile, tile, tile, tile, tile, rep),
                       out_specs=(rep, rep, rep, tile, tile, rep),
                       check_vma=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _train_scan_program(mesh, model: SplitModel, k_iters: int, n_tiers: int,
                        compute_dtype: str = "f32"):
    """Compile-once sharded whole-run loop: ``shard_map(lax.scan(round))``.

    The sharded twin of ``repro.fl.cohort.train_scan``: per-round slot
    tensors arrive stacked with a leading round axis (sharded on axis 1,
    ``repro.sharding.STACKED_SLOT_SPEC``), the scan runs *inside* the
    mapped body so each mesh device sweeps its own slot shard through all
    rounds and the per-round FedAvg is the same masked-psum reduction the
    per-round program uses (:func:`_fedavg_psum`). Carries (params,
    per-gateway losses), applies the same no-trainer/trained-only guards as
    the single-host scan, returns (params, losses, (T, M) loss history,
    (T,) in-scan test hits — see ``repro.fl.cohort._eval_hits``), all
    replicated (every mesh device evaluates the replicated params on the
    replicated test set; identical math, identical hits).
    """

    def body(params, losses0, xs, ys, masks, ws, gws, trained, lr,
             eval_mask, x_test, y_test):
        obs.count("trace.shard.train_scan")
        x_eval = model.prepare_inputs(x_test)

        def step(carry, x):
            params, losses = carry
            xs_t, ys_t, masks_t, w_t, gw_t, tr_t, ev_t = x
            xs_t = cohort_lib._maybe_flatten(model, xs_t)
            final_t, loss_t = cohort_lib._local_train(
                model, params, xs_t, ys_t, masks_t, k_iters, lr,
                compute_dtype)
            final = cohort_lib._concat_tiers(final_t)   # local slots only
            new_global, gw_loss, _, w_sum = _fedavg_psum(
                final, jnp.concatenate(w_t), jnp.concatenate(loss_t),
                jnp.concatenate(gw_t))
            params, losses = cohort_lib._commit_round(
                params, new_global, losses, gw_loss, w_sum > 0, tr_t)
            hits = cohort_lib._eval_hits(model, params, x_eval, y_test,
                                         ev_t)
            return (params, losses), (losses, hits)

        (params, losses), (loss_hist, hits) = jax.lax.scan(
            step, (params, losses0),
            (xs, ys, masks, ws, gws, trained, eval_mask))
        return params, losses, loss_hist, hits

    stk, rep = STACKED_SLOT_SPEC, REPLICATED
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(rep, rep, stk, stk, stk, stk, stk, rep, rep,
                                 rep, rep, rep),
                       out_specs=(rep, rep, rep, rep),
                       check_vma=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _train_scan_program_traced(mesh, model: SplitModel, k_iters: int,
                               n_tiers: int, compute_dtype: str,
                               tier_widths: Tuple[int, ...]):
    """The sharded twin of ``repro.fl.cohort.train_scan_traced``: the data
    plane lives inside the mapped body.

    The device-resident shard stacks (``x_all``/``y_all``) and the data key
    are replicated; only each round's slot->device assignment (a few int32s
    per slot) is sharded over the mesh, and every mesh device gathers its
    own slots' batches in-scan via the counter-based draw
    (``repro.fl.data.traced_batch_indices``) — so the host ships decision
    tensors, never ``(T, S_k, W_k, ...)`` sample stacks.
    """

    def body(params, losses0, x_all, y_all, pool_lens, batch_lens, data_key,
             ts, slot_devs, ws, gws, trained, lr, eval_mask, x_test,
             y_test):
        obs.count("trace.shard.train_scan")
        x_eval = model.prepare_inputs(x_test)

        def step(carry, x):
            params, losses = carry
            t, sd_t, w_t, gw_t, tr_t, ev_t = x
            gathered = [cohort_lib._gather_tier(x_all, y_all, pool_lens,
                                                batch_lens, data_key, t,
                                                devs, width)
                        for devs, width in zip(sd_t, tier_widths)]
            xs_t = cohort_lib._maybe_flatten(
                model, tuple(g[0] for g in gathered))
            ys_t = tuple(g[1] for g in gathered)
            masks_t = tuple(g[2] for g in gathered)
            final_t, loss_t = cohort_lib._local_train(
                model, params, xs_t, ys_t, masks_t, k_iters, lr,
                compute_dtype)
            final = cohort_lib._concat_tiers(final_t)   # local slots only
            new_global, gw_loss, _, w_sum = _fedavg_psum(
                final, jnp.concatenate(w_t), jnp.concatenate(loss_t),
                jnp.concatenate(gw_t))
            params, losses = cohort_lib._commit_round(
                params, new_global, losses, gw_loss, w_sum > 0, tr_t)
            hits = cohort_lib._eval_hits(model, params, x_eval, y_test,
                                         ev_t)
            return (params, losses), (losses, hits)

        (params, losses), (loss_hist, hits) = jax.lax.scan(
            step, (params, losses0),
            (ts, slot_devs, ws, gws, trained, eval_mask))
        return params, losses, loss_hist, hits

    stk, rep = STACKED_SLOT_SPEC, REPLICATED
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(rep, rep, rep, rep, rep, rep, rep, rep, stk,
                                 stk, stk, rep, rep, rep, rep, rep),
                       out_specs=(rep, rep, rep, rep),
                       check_vma=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _stats_program(mesh, model: SplitModel, sigma_samples: int):
    """Compile-once sharded stats pass: device rows tiled over the mesh;
    only the globally-mixed gradient (for delta_n) crosses shards."""

    def body(params, x, y, mask, mix_w, lr):
        obs.count("trace.shard.stats")
        x = model.prepare_inputs(x)
        grads, sigma, lips = cohort_lib._grads_sigma_lips(
            model, params, x, y, mask, lr, sigma_samples)
        global_g = _psum(jnp.tensordot(mix_w, grads, axes=1))
        delta = jnp.linalg.norm(grads - global_g[None], axis=1)
        return sigma, delta, lips

    tile, rep = SLOT_SPEC, REPLICATED
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(rep, tile, tile, tile, tile, rep),
                       out_specs=(tile, tile, tile),
                       check_vma=False)
    return jax.jit(fn)


def _pad_rows(a: np.ndarray, rows: int) -> np.ndarray:
    """Zero-pad the leading axis of ``a`` up to ``rows``."""
    if a.shape[0] == rows:
        return a
    pad = np.zeros((rows - a.shape[0],) + a.shape[1:], a.dtype)
    return np.concatenate([a, pad])


def sharded_cohort_round(mesh, model: SplitModel, params: Params, batch, l_slot,
                         w_slot, gw_onehot, k_iters: int, lr,
                         with_boundary: bool = True,
                         with_gateway_models: bool = False,
                         compute_dtype: str = "f32") -> Tuple:
    """Run one fused FL round sharded over ``mesh``'s ``"cohort"`` axis.

    Same contract and return convention as
    ``repro.fl.cohort.cohort_round`` (5-tuple, or 6-tuple with the gateway
    models when ``with_gateway_models`` is set); ``batch`` may be a
    ``CohortBatch`` or a ``TieredCohortBatch``. Tiers whose slot count does
    not divide the mesh size are transparently zero-padded (empty slots are
    masked out of every reduction) and the per-slot outputs are trimmed
    back, so all-device layouts work unchanged on any mesh.
    """
    n_mesh = mesh.shape[COHORT_AXIS]
    xs, ys, masks = cohort_lib._batch_tiers(batch)
    sizes = tuple(x.shape[0] for x in xs)
    padded = tuple(-(-s // n_mesh) * n_mesh for s in sizes)

    l_t = cohort_lib._split_tiers(np.asarray(l_slot), sizes)
    w_t = cohort_lib._split_tiers(np.asarray(w_slot), sizes)
    gw_t = cohort_lib._split_tiers(np.asarray(gw_onehot), sizes)

    def pad_all(arrs, dtype=None):
        return tuple(jnp.asarray(_pad_rows(np.asarray(a, dtype), p))
                     for a, p in zip(arrs, padded))

    xs = pad_all(xs)
    ys = pad_all(ys)
    masks = pad_all(masks, np.float32)
    l_t = pad_all(l_t, np.int32)
    w_t = pad_all(w_t, np.float32)
    gw_t = pad_all(gw_t, np.float32)

    fn = _round_program(mesh, model, k_iters, len(sizes),
                        with_boundary, with_gateway_models, compute_dtype)
    new_global, gw_loss, gw_count, loss_t, boundary_t, gw_models = fn(
        _replicated(mesh, params), xs, ys, masks, l_t, w_t, gw_t,
        jnp.float32(lr))

    # trim the per-tier padding back off the per-slot outputs
    dev_losses = jnp.concatenate([v[:s] for v, s in zip(loss_t, sizes)])
    boundary = jnp.concatenate([v[:s] for v, s in zip(boundary_t, sizes)])
    out = (new_global, gw_loss, gw_count, dev_losses, boundary)
    return (*out, gw_models) if with_gateway_models else out


def sharded_cohort_stats(mesh, model: SplitModel, params: Params, batch,
                         mix_weights, lr, sigma_samples: int):
    """sigma/delta/Lipschitz for every device, sharded over ``mesh``.

    Mirrors ``repro.fl.cohort.cohort_stats``: ``batch`` uses the
    all-devices layout (row n = device n); rows are zero-padded to a mesh
    multiple and the padding is trimmed from the outputs.
    """
    n_mesh = mesh.shape[COHORT_AXIS]
    n_dev = batch.x.shape[0]
    rows = -(-n_dev // n_mesh) * n_mesh
    fn = _stats_program(mesh, model, sigma_samples)
    sigma, delta, lips = fn(
        _replicated(mesh, params),
        jnp.asarray(_pad_rows(np.asarray(batch.x), rows)),
        jnp.asarray(_pad_rows(np.asarray(batch.y), rows)),
        jnp.asarray(_pad_rows(np.asarray(batch.mask, np.float32), rows)),
        jnp.asarray(_pad_rows(np.asarray(mix_weights, np.float32), rows)),
        jnp.float32(lr))
    return sigma[:n_dev], delta[:n_dev], lips[:n_dev]


@sim_lib.register_engine("sharded")
class ShardedCohortEngine(sim_lib.CohortEngine):
    """Cohort engine sharded over a 1-D ``"cohort"`` device mesh.

    Drop-in replacement for :class:`repro.fl.sim.CohortEngine` for
    100+-device cohorts: identical packing/telemetry logic, but the fused
    round and stats programs run under ``jax.shard_map`` with device slots
    sharded, parameters replicated, and the two-tier FedAvg reduced via
    masked psums (see the module docstring). ``Scenario.mesh_shape`` picks
    the mesh size (``None`` = every addressable device, one on a
    single-device host, with identical numerics; more devices than the
    process has raises).
    """

    def _mesh(self, sim: "sim_lib.Simulation"):
        """The (cached) cohort mesh this simulation's scenario asked for."""
        return cohort_mesh(sim.scenario.mesh_shape)

    def _shard_count(self, sim: "sim_lib.Simulation") -> int:
        """Tier slot counts must divide the cohort mesh size."""
        return int(self._mesh(sim).shape[COHORT_AXIS])

    def _fused_round(self, sim: "sim_lib.Simulation", params, batch, l_slot,
                     w_slot, gw_slot, *, with_boundary: bool,
                     with_gateway_models: bool):
        """Run the round under shard_map instead of on a single device."""
        sc = sim.scenario
        out = sharded_cohort_round(
            self._mesh(sim), sim.plan, params, batch, l_slot, w_slot,
            gw_slot, sc.k_iters, sc.lr, with_boundary=with_boundary,
            with_gateway_models=with_gateway_models,
            compute_dtype=sc.dtype)
        return out if with_gateway_models else (*out, None)

    def _fused_stats(self, sim: "sim_lib.Simulation", params, batch, mix):
        """Run the sigma/delta/L_n program under shard_map (same rng draws
        and DataStats post-processing as the single-host cohort engine, so
        engines stay swappable)."""
        sc = sim.scenario
        return sharded_cohort_stats(self._mesh(sim), sim.plan, params,
                                    batch, mix, sc.lr, sc.sigma_samples)

    def fused_train(self, sim: "sim_lib.Simulation", params, losses0, xs,
                    ys, masks, ls, ws, gws, trained, eval_mask=None):
        """All rounds as one sharded program: ``shard_map(lax.scan)`` with
        each tier's slot axis split over the cohort mesh (the engine's
        layout already rounds tier slot counts to mesh multiples, so the
        stacked arrays shard evenly — no padding pass needed). ``ls`` is
        unused (no boundary telemetry inside the scan)."""
        sc = sim.scenario
        if eval_mask is None:
            eval_mask = np.zeros(trained.shape[0], bool)
        mesh = self._mesh(sim)
        fn = _train_scan_program(mesh, sim.plan, sc.k_iters, len(xs),
                                 sc.dtype)
        x_test, y_test = self._eval_arrays(sim)
        return obs.call_keeping(
            "train_scan", "trace.shard.train_scan", fn,
            _replicated(mesh, params),
            jnp.asarray(np.asarray(losses0), jnp.float32),
            xs, ys, masks, ws, gws, trained, jnp.float32(sc.lr),
            jnp.asarray(np.asarray(eval_mask, bool)), x_test, y_test)

    def fused_train_traced(self, sim: "sim_lib.Simulation", params, losses0,
                           ts, slot_devs, ls, ws, gws, trained, eval_mask,
                           layout):
        """The traced-data-plane whole-run program, sharded: replicated
        shard stacks + mesh-sharded slot assignments (see
        :func:`_train_scan_program_traced`). ``ls`` is unused, as in
        :meth:`fused_train`."""
        sc = sim.scenario
        x_all, y_all, pool = self._data_stacks(sim)
        batch_lens = np.minimum(
            np.asarray(sim.d_tilde, np.int32), pool).astype(np.int32)
        mesh = self._mesh(sim)
        fn = _train_scan_program_traced(
            mesh, sim.plan, sc.k_iters, len(slot_devs), sc.dtype,
            tuple(layout.tier_widths))
        x_test, y_test = self._eval_arrays(sim)
        return obs.call_keeping(
            "train_scan", "trace.shard.train_scan", fn,
            _replicated(mesh, params),
            jnp.asarray(np.asarray(losses0), jnp.float32),
            x_all, y_all, jnp.asarray(pool), jnp.asarray(batch_lens),
            sim.data_key, jnp.asarray(np.asarray(ts, np.int32)), slot_devs,
            ws, gws, trained, jnp.float32(sc.lr),
            jnp.asarray(np.asarray(eval_mask, bool)), x_test, y_test)
