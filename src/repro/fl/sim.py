"""Composable FL simulation API: Scenario / Policy / Engine protocols.

The simulation surface is built from three explicit, independently pluggable
protocols:

* **Scenario** — a frozen, JSON-serializable spec of everything that defines
  an experiment: network config, data distribution, model (resolved through
  ``repro.models.registry.build_fl_model``), local-training hyperparameters
  and the default policy/engine names.
* **Policy** — any object with ``schedule(ctx) -> RoundDecision``; named
  policies come from the decorator registry in ``repro.core.schedulers``
  (``make_policy`` threads registry-declared kwargs such as ``seed``).
* **Engine** — how a scheduled round is physically executed:
  ``CohortEngine`` (one fused XLA program per round, ``repro.fl.cohort``),
  ``ShardedCohortEngine`` (the same fused round mapped over a 1-D
  ``"cohort"`` device mesh via ``jax.shard_map``, ``repro.fl.shard``) or
  ``SequentialEngine`` (the seed per-device loop, kept as the parity
  reference). All implement ``estimate_stats`` + ``train_round``.

On top sits :class:`Simulation`: a streaming ``rounds()`` generator yielding
one :class:`RoundRecord` per round (decision, delay, gateway losses, queue
state, optional boundary-activation RMS), with ``run()`` as a thin consumer
returning the classic :class:`FLResult`, ``reset(seed)`` restoring params,
batch RNG **and** network channel-state RNG together (fair multi-policy
sweeps), and ``save()``/``Simulation.resume()`` wired through
``repro.checkpoint.store`` for bit-identical checkpoint-resume.
"""
from __future__ import annotations

import atexit
import dataclasses
import json
import pathlib
import queue
import re
import threading
import warnings
from typing import Dict, Iterator, List, Optional, Tuple, Type, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.checkpoint import store
from repro.core import costmodel as cm
from repro.core.ddsra import RoundDecision, Workload
from repro.core.lyapunov import update_queues_realized
from repro.core.network import Network, NetworkConfig
from repro.core.participation import (DataStats, divergence_bound,
                                      participation_rates)
from repro.core.schedulers import (POLICIES, RoundContext, make_policy,
                                   policy_state, set_policy_state)
from repro.fl import cohort as cohort_lib
from repro.fl import split as split_lib
from repro.fl.data import (CohortLayout, device_resident_stacks,
                           make_fl_dataset, make_token_fl_dataset,
                           sample_batch, sample_cohort_batch,
                           sample_cohort_batch_traced)
from repro.fl.faults import FaultModel
from repro.fl.roles import BaseStation, Device, Gateway
from repro.models import registry as model_registry


# ---------------------------------------------------------------------------
# Scenario
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Scenario:
    """Frozen, JSON-serializable spec of one FL experiment.

    Everything that defines a run lives here: the network/topology config
    (``net``), the data distribution (``alpha``/``chi``/``max_dataset``),
    the model (a ``repro.models.registry`` name), local-training
    hyperparameters, the default policy/engine names, and the execution
    layout for the cohort engines (``tiers`` tiered slot widths — an int
    or ``"auto"``; ``mesh_shape`` for the sharded engine's cohort mesh).
    ``to_json``/``from_json`` round-trip exactly, and checkpoints written
    before a field existed load with its default.
    """
    model: str = "vgg"                 # repro.models.registry.FL_MODELS key
    width_mult: float = 0.25
    classes: int = 10
    mlp_hidden: Tuple[int, ...] = (128, 64)
    seq_len: int = 32                  # sequence length for token models
    k_iters: int = 5                   # local epochs K
    lr: float = 0.01                   # step size beta
    alpha: float = 0.05                # training data sampling ratio
    rounds: int = 50
    v: float = 0.01                    # Lyapunov control parameter
    policy: str = "ddsra"              # default scheduling policy name
    seed: int = 0
    eval_every: int = 5
    max_dataset: int = 2000
    chi: float = 1.0                   # non-IID degree
    sigma_samples: int = 8             # per-sample grads for sigma estimation
    engine: str = "cohort"             # ENGINES key
    # tiered slot widths: an int (1 = single width) or "auto" to pick the
    # tier count from the d_tilde histogram (CohortLayout.auto_tiers —
    # smallest count reaching the padded-samples curve's floor)
    tiers: Union[int, str] = 1
    mesh_shape: Optional[Tuple[int, ...]] = None   # cohort mesh (None = all)
    keep_last: Optional[int] = None    # checkpoint rotation (None = keep all)
    # mixed-precision data plane: "f32" (default) or "bf16" (bf16 storage/
    # GEMMs with f32 master params + f32 accumulation; cohort engines only)
    dtype: str = "f32"
    # where training batches are drawn: "host" (numpy RNG draws replayed /
    # pre-packed per round) or "traced" (counter-based jax draws gathered
    # from device-resident shard stacks — inside the scan on the fused
    # path; cohort engines only, see repro.fl.data.traced_batch_indices)
    data_plane: str = "host"
    # model-upload compression: bits per parameter priced into the DDSRA
    # upload-delay/energy terms (None = the model's native precision;
    # dtype="bf16" implies 16 unless overridden — e.g. 8 for int8 uploads)
    upload_bits: Optional[float] = None
    # fault-injection axes (engine="async" only; see repro.fl.faults):
    # per-round, per-device probabilities of being offline at dispatch
    # (churn), of losing the trained update mid-round (dropout), and of
    # straggling — an Exp(mean=straggler_scale) multiplicative extra delay
    # factor fires with probability straggler_frac. All zero = no faults.
    churn: float = 0.0
    dropout: float = 0.0
    straggler_frac: float = 0.0
    straggler_scale: float = 0.0
    # FedBuff-style buffered aggregation (engine="async"): aggregate once
    # buffer_k gateway updates have landed; None = drain the round's whole
    # dispatched cohort first (the synchronous barrier expressed in
    # buffered form — the degenerate-parity oracle against CohortEngine).
    buffer_k: Optional[int] = None
    # staleness weighting s(tau) = (1 + tau)^(-alpha) applied to buffered
    # updates tau aggregation-versions old (0.5 = FedBuff's 1/sqrt(1+tau));
    # updates older than max_staleness versions are discarded (None = keep).
    staleness_alpha: float = 0.5
    max_staleness: Optional[int] = None
    net: NetworkConfig = dataclasses.field(default_factory=NetworkConfig)

    @property
    def effective_upload_bits(self) -> Optional[float]:
        """Bits per parameter the cost model prices the model upload at:
        ``upload_bits`` when set, else 16 for the bf16 data plane, else
        ``None`` — the model's native precision
        (``costmodel.upload_bytes(layers, None)`` = ``model_size_bytes``)."""
        if self.upload_bits is not None:
            return float(self.upload_bits)
        return 16.0 if self.dtype == "bf16" else None

    def to_json(self) -> dict:
        """Serialize to a plain-JSON dict (tuples become lists)."""
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "Scenario":
        """Rebuild from :meth:`to_json` output, tolerating version skew in
        both directions: fields *missing* from ``d`` (checkpoints/sweep
        JSONs written before the field existed) take their dataclass
        defaults, and *unknown* fields (written by a newer version) are
        dropped with a warning instead of raising — so old artifacts keep
        loading after new axes land, and new artifacts degrade gracefully
        on old code. The same applies to the nested ``net`` config."""
        d = dict(d)
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(d) - known)
        if unknown:
            warnings.warn(
                f"Scenario.from_json: ignoring unknown fields {unknown} "
                "(written by a newer version?)", stacklevel=2)
            for k in unknown:
                d.pop(k)
        net = d.pop("net", {})
        if isinstance(net, dict):
            net = dict(net)
            net_known = {f.name for f in dataclasses.fields(NetworkConfig)}
            net_unknown = sorted(set(net) - net_known)
            if net_unknown:
                warnings.warn(
                    "Scenario.from_json: ignoring unknown net fields "
                    f"{net_unknown} (written by a newer version?)",
                    stacklevel=2)
                for k in net_unknown:
                    net.pop(k)
            for k in ("f_dev_range", "dist_range"):
                if k in net:
                    net[k] = tuple(net[k])
            net = NetworkConfig(**net)
        d["mlp_hidden"] = tuple(d.get("mlp_hidden", (128, 64)))
        if d.get("mesh_shape") is not None:
            d["mesh_shape"] = tuple(d["mesh_shape"])
        return cls(net=net, **d)


# ---------------------------------------------------------------------------
# RoundRecord / FLResult
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RoundRecord:
    """Telemetry for one simulated round (yielded by Simulation.rounds()).

    The staleness/fault fields are filled by the buffered async engine
    (``repro.fl.async_engine``); synchronous engines leave them at their
    barrier-semantics values (one aggregation per trained round, staleness
    0, no faults).
    """
    t: int
    selected: np.ndarray               # (M,) gateway participation this round
    trained: List[int]                 # gateways that actually trained
    l_n: np.ndarray                    # (N,) per-device partition points
    delay: float                       # realized round delay (time advanced)
    cum_delay: float
    queues: np.ndarray                 # (M,) virtual-queue backlog
    losses: np.ndarray                 # (M,) per-gateway local losses
    failures: int                      # resource-infeasible gateways
    boundary_rms: Optional[np.ndarray] = None   # (N,) when requested
    accuracy: Optional[float] = None   # test accuracy on eval rounds
    # -- staleness / fault telemetry (async engine) ----------------------
    aggregations: int = 0              # buffer flushes applied this round
    staleness_mean: float = 0.0        # mean tau over updates aggregated
    staleness_max: int = 0             # max tau over updates aggregated
    stale_discarded: int = 0           # updates dropped for tau > max_staleness
    dropped_devices: int = 0           # churned offline at dispatch
    lost_devices: int = 0              # trained, update lost mid-round
    straggler_devices: int = 0         # surviving devices that straggled
    buffer_fill: int = 0               # buffer occupancy at round end
    inflight: int = 0                  # updates still in flight at round end


def resolve_decision(dec: RoundDecision, gateways, n_devices: int):
    """Resolve a schedule into what actually trains this round.

    The host-side half of the decision contract: for each selected gateway,
    look up its assigned channel's solution, fail it (counted) when the
    solve is infeasible or non-finite, and scatter the per-lane partition
    points of surviving gateways into the dense (N,) vector. The traced
    twin is ``repro.core.ddsra_jax.resolve_decision_arrays`` — identical
    semantics over :class:`~repro.core.ddsra_jax.DecisionArrays`, pinned
    bit-identical by ``tests/test_fused_sim.py``.

    Returns ``(trained, l_n, gw_delay, failures)``: the trained gateway
    ids (ascending), the (N,) per-device partition points, the per-gateway
    realized delays and the infeasible-selection count.
    """
    trained, l_n = [], np.zeros(n_devices, int)
    gw_delay: Dict[int, float] = {}
    failures = 0
    for m in np.where(dec.selected)[0]:
        j = int(np.argmax(dec.assignment[m]))
        sol = dec.solutions.get((int(m), j))
        if sol is None:
            continue
        if not sol.feasible or not np.isfinite(sol.delay):
            failures += 1     # energy/memory violation: round fails
            continue
        gw_delay[int(m)] = float(sol.delay)
        trained.append(int(m))
        for i, dev in enumerate(gateways[m].devices):
            l_n[dev.idx] = int(sol.l_split[i])
    return trained, l_n, gw_delay, failures


@dataclasses.dataclass
class FLResult:
    """Aggregate outcome of a full run (built by ``Simulation.result_of``)."""
    accuracy: List[float]
    acc_rounds: List[int]
    cum_delay: List[float]
    participation: np.ndarray          # (T, M)
    gamma_targets: np.ndarray
    losses: List[float]
    phi: np.ndarray
    failures: int


# ---------------------------------------------------------------------------
# Engines
# ---------------------------------------------------------------------------

ENGINES: Dict[str, Type["Engine"]] = {}


def register_engine(name: str):
    """Class decorator: register an :class:`Engine` under ``name`` (the
    value a ``Scenario.engine`` field refers to). Duplicate names raise."""
    def deco(cls):
        if name in ENGINES:
            raise ValueError(f"engine {name!r} already registered")
        ENGINES[name] = cls
        cls.name = name
        return cls
    return deco


def make_engine(name: str) -> "Engine":
    """Instantiate a registered engine by name (see ``ENGINES``)."""
    if name not in ENGINES:
        raise ValueError(f"unknown engine {name!r}: "
                         f"expected one of {sorted(ENGINES)}")
    return ENGINES[name]()


@dataclasses.dataclass
class RoundOutcome:
    """What actually happened when an engine executed a scheduled round.

    Synchronous engines realize exactly what was scheduled (``realized``
    stays ``None`` — the policy's own queue update stands); the buffered
    async engine reports realized completion instead: the time actually
    advanced (straggler tails included), which gateways' updates actually
    landed, and the staleness/fault telemetry threaded into
    :class:`RoundRecord`.
    """
    delay: float                       # realized time advanced this round
    boundary_rms: Optional[np.ndarray] = None
    # (M,) bool realized participation indicator for the Lyapunov queue
    # update (lyapunov.update_queues_realized); None = as scheduled.
    realized: Optional[np.ndarray] = None
    aggregations: int = 0
    staleness_mean: float = 0.0
    staleness_max: int = 0
    stale_discarded: int = 0
    dropped_devices: int = 0
    lost_devices: int = 0
    straggler_devices: int = 0
    buffer_fill: int = 0
    inflight: int = 0


class Engine:
    """Protocol: how a scheduled round is executed on the model."""
    name: str
    # compute dtypes this engine can run the data plane in; Simulation
    # rejects a Scenario whose ``dtype`` the chosen engine can't honor
    # (silently training in f32 would falsify the priced upload_bits).
    supported_dtypes: Tuple[str, ...] = ("f32",)
    # whether the engine honors the Scenario fault axes (churn/dropout/
    # stragglers) and buffer_k; Simulation rejects active fault axes on
    # engines that would silently train fault-free (falsified sweeps).
    supports_faults: bool = False
    # whether :meth:`fused_train` runs the whole-trajectory scan (the fused
    # simulation loop, ``repro.fl.fused_sim``); engines without it are
    # refused up front, before any RNG stream is consumed.
    supports_fused: bool = False
    # whether the engine honors ``Scenario.data_plane="traced"`` (counter-
    # based jax batch draws instead of the host numpy stream); Simulation
    # rejects traced-plane scenarios on engines that would silently keep
    # sampling host-side (the two planes draw different batches).
    supports_traced_data: bool = False

    def estimate_stats(self, sim: "Simulation", params) -> DataStats:
        """Estimate the per-device sigma_n/delta_n/L_n statistics the
        divergence bound (paper Sec. VII-A) needs."""
        raise NotImplementedError

    def train_round(self, sim: "Simulation", trained: List[int],
                    l_n: np.ndarray,
                    with_boundary: bool = False) -> Optional[np.ndarray]:
        """Train one round in-place on ``sim`` (params + per-gateway losses);
        returns the (N,) boundary-activation RMS when requested/supported."""
        raise NotImplementedError

    def run_round(self, sim: "Simulation", dec: RoundDecision,
                  trained: List[int], l_n: np.ndarray,
                  gw_delay: Dict[int, float],
                  boundary: bool = False) -> RoundOutcome:
        """Execute one scheduled round and report what actually happened.

        Default (synchronous) semantics: train the scheduled cohort via
        :meth:`train_round`, realize exactly the scheduled delays (the
        FedAvg barrier waits for the slowest gateway, ``max`` over
        ``gw_delay``), and leave the policy's queue update untouched. The
        async engine overrides this wholesale — buffered aggregation,
        fault injection, realized-delay accounting.
        """
        rms = self.train_round(sim, trained, l_n, with_boundary=boundary)
        return RoundOutcome(delay=max(gw_delay.values(), default=0.0),
                            boundary_rms=rms,
                            aggregations=1 if trained else 0)

    def inflight_counts(self, sim: "Simulation") -> Optional[np.ndarray]:
        """(M,) per-gateway count of dispatched-but-not-landed updates,
        offered to policies via ``RoundContext.inflight``; synchronous
        engines have none (``None``)."""
        return None

    def fused_train(self, sim: "Simulation", params, losses0, xs, ys,
                    masks, ls, ws, gws, trained, eval_mask=None):
        """Run a whole pre-packed training trajectory as one compiled
        program (the fused simulation loop, ``repro.fl.fused_sim``).

        ``xs/ys/masks/ls/ws/gws`` are per-tier tuples with a leading round
        axis (tier k: ``(T, S_k, ...)``), ``trained`` the (T, M) bool
        trained-gateway mask, ``eval_mask`` the (T,) bool ``eval_every``
        schedule (None = never evaluate). Returns (final params, final
        (M,) losses, (T, M) per-round loss history, (T,) in-scan test
        hits — -1 on non-eval rounds). Engines without a scan-compatible
        round (the sequential loop, the buffered async engine) raise —
        ``Simulation.rounds()`` is their only path.
        """
        raise NotImplementedError(
            f"engine {self.name!r} has no fused scan path; use "
            "Simulation.rounds()")

    def reset(self, sim: "Simulation") -> None:
        """Discard engine-internal *run* state (default: none).

        Called from :meth:`Simulation.restart` — and therefore from
        ``run()`` and ``reset()`` — so buffered engines drop in-flight and
        parked updates when the clock rewinds; a stale update from a
        previous run must never aggregate into a fresh one."""
        return None

    def state_dict(self, sim: "Simulation"):
        """Engine-internal state to checkpoint, as ``(meta, arrays)`` —
        ``meta`` a JSON-serializable dict stored in the ``sim_*.json``
        manifest, ``arrays`` a pytree written beside the params (prefix
        ``engine_``) — or ``None`` for stateless engines (the default)."""
        return None

    def load_state_dict(self, sim: "Simulation", meta: dict, path,
                        step: int) -> None:
        """Restore what :meth:`state_dict` captured (default: nothing)."""
        return None


@register_engine("cohort")
class CohortEngine(Engine):
    """One fused XLA program per round (see ``repro.fl.cohort``).

    Participants are packed into a fixed tier-major slot layout
    (``repro.fl.data.CohortLayout`` — ``Scenario.tiers`` controls how many
    distinct slot widths are used; 1 reproduces the historical single-width
    contract), so every round reuses one compiled executable regardless of
    which devices the policy schedules.
    """

    supported_dtypes = ("f32", "bf16")
    supports_fused = True
    supports_traced_data = True

    def _shard_count(self, sim: "Simulation") -> int:
        """Multiple each tier's slot count must divide into (the cohort
        mesh size for the sharded subclass; 1 on a single host)."""
        return 1

    def _layout(self, sim: "Simulation", capacity: int) -> CohortLayout:
        """The (cached) fixed slot layout for ``capacity``-slot rounds."""
        key = (capacity, sim.scenario.tiers, self._shard_count(sim))
        if key not in sim._layouts:
            sim._layouts[key] = CohortLayout.build(
                sim.d_tilde, capacity, sim.scenario.tiers,
                self._shard_count(sim))
        return sim._layouts[key]

    def _fused_round(self, sim: "Simulation", params, batch, l_slot, w_slot,
                     gw_slot, *, with_boundary: bool,
                     with_gateway_models: bool):
        """Execute one fused round; subclasses override this to change
        *where* it runs (e.g. sharded over a mesh) without touching the
        packing/telemetry logic above it. Always returns the 6-tuple
        (new_global, gw_loss, gw_count, slot_losses, boundary, gw_models)
        with ``gw_models=None`` when not requested."""
        sc = sim.scenario
        out = cohort_lib.cohort_round(
            sim.plan, params, batch, l_slot, w_slot, gw_slot,
            sc.k_iters, sc.lr, with_boundary=with_boundary,
            with_gateway_models=with_gateway_models,
            compute_dtype=sc.dtype)
        return out if with_gateway_models else (*out, None)

    def _fused_stats(self, sim: "Simulation", params, batch, mix):
        """Run the fused sigma/delta/L_n program; the sharded subclass
        overrides this (only) to run it under shard_map."""
        sc = sim.scenario
        return cohort_lib.cohort_stats(sim.plan, params, batch, mix, sc.lr,
                                       sc.sigma_samples)

    def estimate_stats(self, sim: "Simulation", params) -> DataStats:
        """sigma/delta/Lipschitz for every device in one fused program."""
        n_dev = sim.net.cfg.n_devices
        batch = sample_cohort_batch(sim.rng, sim.ds, range(n_dev),
                                    sim.d_tilde, int(sim.d_tilde.max()))
        mix = sim.d_sizes / sim.d_sizes.sum()
        sigma, delta, lips = self._fused_stats(sim, params, batch, mix)
        return DataStats(np.asarray(sigma), np.asarray(delta),
                         np.maximum(np.asarray(lips), 0.1),
                         sim.d_tilde.astype(float))

    def _pack_round(self, sim: "Simulation", trained: List[int],
                    l_n: np.ndarray):
        """Pack the scheduled devices into the fixed slot layout.

        Owns the batch-draw ordering contract (draws come from ``sim.rng``
        in gateway-major device order, identical for every engine built on
        this packing — the async engine reuses it verbatim so its degenerate
        configuration replays the cohort engine's exact RNG stream).
        Returns (device_ids, batch, layout, l_slot, w_slot, slot_gw).
        """
        device_ids: List[int] = []
        for m in trained:
            device_ids.extend(dev.idx for dev in sim.gateways[m].devices)
        # capacity always fits a schedulable round; fall back to the all-
        # devices layout (one extra compile, same numerics) if it ever won't
        cap = sim.cohort_capacity if len(device_ids) <= sim.cohort_capacity \
            else sim.net.cfg.n_devices
        layout = self._layout(sim, cap)
        if sim.scenario.data_plane == "traced":
            # counter-based jax draws (a pure function of (data_key, round,
            # device)) — no host RNG consumed, bit-identical to the fused
            # scan's in-program gathers
            batch = sample_cohort_batch_traced(sim.data_key, sim.t, sim.ds,
                                               device_ids, sim.d_tilde,
                                               layout=layout)
        else:
            batch = sample_cohort_batch(sim.rng, sim.ds, device_ids,
                                        sim.d_tilde, layout=layout)
        n_slots = layout.n_slots
        l_slot = np.zeros(n_slots, int)
        w_slot = np.zeros(n_slots, np.float32)
        slot_gw = np.zeros((n_slots, sim.net.cfg.n_gateways), np.float32)
        for di, n in enumerate(device_ids):
            s = int(batch.slot_of[di])
            l_slot[s] = l_n[n]
            w_slot[s] = sim.d_tilde[n]
            slot_gw[s, sim.net.assign[n]] = 1.0
        return device_ids, batch, layout, l_slot, w_slot, slot_gw

    def train_round(self, sim: "Simulation", trained: List[int],
                    l_n: np.ndarray,
                    with_boundary: bool = False) -> Optional[np.ndarray]:
        """Pack the scheduled devices into the fixed slot layout and run
        the fused round in-place on ``sim``."""
        if not trained:
            return None
        device_ids, batch, layout, l_slot, w_slot, slot_gw = \
            self._pack_round(sim, trained, l_n)
        new_global, gw_loss, _, _, boundary, _ = self._fused_round(
            sim, sim.params, batch, l_slot, w_slot, slot_gw,
            with_boundary=with_boundary, with_gateway_models=False)
        sim.params = new_global
        # padded-vs-real sample accounting (read by fl_round_bench)
        sim.padding_stats["real_samples"] += float(
            sum(t.mask.sum() for t in batch.tiers))
        sim.padding_stats["padded_samples"] += float(layout.padded_samples)
        gw_loss = np.asarray(gw_loss)
        for m in trained:
            sim.losses[m] = float(gw_loss[m])
        if with_boundary:
            rms = np.zeros(sim.net.cfg.n_devices)
            rms[device_ids] = np.asarray(boundary)[batch.slot_of]
            return rms
        return None

    def fused_train(self, sim: "Simulation", params, losses0, xs, ys,
                    masks, ls, ws, gws, trained, eval_mask=None):
        """All rounds as one program: ``lax.scan`` of the fused round
        (``repro.fl.cohort.train_scan``) over the stacked packed batches
        and decision tensors."""
        sc = sim.scenario
        if eval_mask is None:
            eval_mask = np.zeros(np.asarray(trained).shape[0], bool)
        x_test, y_test = self._eval_arrays(sim)
        return obs.call_keeping(
            "train_scan", "trace.cohort.train_scan", cohort_lib.train_scan,
            sim.plan, params, losses0, xs, ys, masks, ls, ws, gws, trained,
            np.float32(sc.lr), np.asarray(eval_mask, bool),
            x_test, y_test,
            k_iters=sc.k_iters, compute_dtype=sc.dtype)

    def _pack_round_meta(self, sim: "Simulation", trained: List[int],
                         l_n: np.ndarray):
        """:meth:`_pack_round`'s slot assignment WITHOUT sampling any data
        — the traced data plane's packing: the fused scan gathers each
        slot's batch in-program from its device id, so the host only ships
        this round's (slot -> device, l, weight, gateway) metadata.

        Slot ranks replicate ``sample_cohort_batch_traced``'s assignment
        exactly (same stable argsort over the same clipped batch lengths),
        so per-slot outputs scatter back to devices identically on both
        paths. Returns (device_ids, layout, slot_dev (-1 = empty slot),
        l_slot, w_slot, slot_gw, real_samples).
        """
        device_ids: List[int] = []
        for m in trained:
            device_ids.extend(dev.idx for dev in sim.gateways[m].devices)
        cap = sim.cohort_capacity if len(device_ids) <= sim.cohort_capacity \
            else sim.net.cfg.n_devices
        layout = self._layout(sim, cap)
        pools = np.array([len(sim.ds.y_dev[n]) for n in device_ids],
                         dtype=int)
        lens = np.minimum(sim.d_tilde[device_ids], pools) if device_ids \
            else np.zeros(0, dtype=int)
        n_slots = layout.n_slots
        slot_dev = np.full(n_slots, -1, np.int32)
        l_slot = np.zeros(n_slots, int)
        w_slot = np.zeros(n_slots, np.float32)
        slot_gw = np.zeros((n_slots, sim.net.cfg.n_gateways), np.float32)
        for rank, di in enumerate(np.argsort(-lens, kind="stable")):
            n = device_ids[di]
            slot_dev[rank] = n
            l_slot[rank] = l_n[n]
            w_slot[rank] = sim.d_tilde[n]
            slot_gw[rank, sim.net.assign[n]] = 1.0
        return (device_ids, layout, slot_dev, l_slot, w_slot, slot_gw,
                int(lens.sum()))

    def _data_stacks(self, sim: "Simulation"):
        """The (lazily-built, cached) device-resident shard stacks the
        traced data plane gathers from (``repro.fl.data
        .device_resident_stacks``); the dataset is fixed per Simulation,
        so the cache survives reset/restart. The x/y stacks are committed
        to device here — caching host arrays would re-transfer the full
        pool (tens of MB) on every fused call, a fixed cost that dwarfs
        the scan itself; ``pool`` stays numpy for host-side arithmetic."""
        if getattr(sim, "_resident_stacks", None) is None:
            x_all, y_all, pool = device_resident_stacks(sim.ds)
            sim._resident_stacks = (jnp.asarray(x_all), jnp.asarray(y_all),
                                    pool)
        return sim._resident_stacks

    def _eval_arrays(self, sim: "Simulation"):
        """Device-committed (x_test, y_test), cached for the same reason
        as :meth:`_data_stacks`."""
        if getattr(sim, "_resident_eval", None) is None:
            sim._resident_eval = (jnp.asarray(sim.ds.x_test),
                                  jnp.asarray(sim.ds.y_test))
        return sim._resident_eval

    def fused_train_traced(self, sim: "Simulation", params, losses0, ts,
                           slot_devs, ls, ws, gws, trained, eval_mask,
                           layout):
        """All rounds as one program with the data plane *inside* it:
        ``repro.fl.cohort.train_scan_traced`` gathers every round's batches
        in-scan from the device-resident shard stacks, so the host never
        materializes the ``(T, S_k, W_k, ...)`` sample stacks
        :meth:`fused_train` is fed. ``slot_devs/ls/ws/gws`` are per-tier
        tuples with a leading round axis; ``ts`` the absolute round
        indices the counter-based draws fold in."""
        sc = sim.scenario
        x_all, y_all, pool = self._data_stacks(sim)
        batch_lens = np.minimum(
            np.asarray(sim.d_tilde, np.int32), pool).astype(np.int32)
        x_test, y_test = self._eval_arrays(sim)
        return obs.call_keeping(
            "train_scan", "trace.cohort.train_scan",
            cohort_lib.train_scan_traced,
            sim.plan, params, losses0, x_all, y_all, pool, batch_lens,
            sim.data_key, np.asarray(ts, np.int32), slot_devs, ls, ws, gws,
            trained, np.float32(sc.lr), np.asarray(eval_mask, bool),
            x_test, y_test, k_iters=sc.k_iters,
            compute_dtype=sc.dtype, tier_widths=tuple(layout.tier_widths))

    def shop_floor_round(self, sim: "Simulation", device_ids: List[int],
                         l_n: np.ndarray, params=None,
                         rng: Optional[np.random.Generator] = None):
        """Fused round over ``device_ids`` that also surfaces the per-gateway
        shop-floor models (the intermediate the Fig. 2 divergence experiment
        compares against a centralized twin).

        Batches are drawn from ``rng`` in ``device_ids`` order — exactly the
        draws the sequential per-device loop would make — and returned so the
        caller can, e.g., pool them for a centralized-GD twin. This path
        keeps the all-devices layout (row n = device n) so ``l_n``/weights
        index devices directly.

        Returns (new_global, gateway_models (leading M axis), gateway_losses,
        CohortBatch).
        """
        rng = sim.rng if rng is None else rng
        params = sim.params if params is None else params
        weights = np.zeros(sim.net.cfg.n_devices, np.float32)
        weights[list(device_ids)] = sim.d_tilde[list(device_ids)]
        batch = sample_cohort_batch(rng, sim.ds, device_ids, sim.d_tilde,
                                    int(sim.d_tilde.max()))
        new_global, gw_loss, _, _, _, gw_models = self._fused_round(
            sim, params, batch, l_n, weights, sim.net.a,
            with_boundary=False, with_gateway_models=True)
        return new_global, gw_models, np.asarray(gw_loss), batch


@register_engine("sequential")
class SequentialEngine(Engine):
    """Seed per-device Python loop (kept as the parity/bench reference)."""

    def estimate_stats(self, sim: "Simulation", params) -> DataStats:
        """sigma/delta/Lipschitz estimated one device at a time (the seed
        O(devices x samples) loop of jitted calls)."""
        sc = sim.scenario
        n_dev = sim.net.cfg.n_devices
        grads, sigmas, lips = [], [], []
        for n in range(n_dev):
            x, y = sample_batch(sim.rng, sim.ds, n, sim.d_tilde[n])
            g = np.asarray(split_lib.flat_grad(sim.plan, params, x, y))
            grads.append(g)
            # sigma: per-sample gradient spread
            m_s = min(sc.sigma_samples, len(y))
            per = [np.asarray(split_lib.flat_grad(sim.plan, params,
                                                  x[i:i + 1], y[i:i + 1]))
                   for i in range(m_s)]
            mean_g = np.mean(per, axis=0)
            sigmas.append(float(np.mean([np.linalg.norm(p - mean_g)
                                         for p in per])))
            # L_n: two-point secant
            w0 = split_lib.flat_params(params)
            pert = jax.tree.map(
                lambda p_, gg: p_ - sc.lr * gg,
                params, jax.tree.unflatten(jax.tree.structure(params),
                                           _unflatten_like(g, params)))
            g2 = np.asarray(split_lib.flat_grad(sim.plan, pert, x, y))
            w1 = split_lib.flat_params(pert)
            dw = np.linalg.norm(np.asarray(w1) - np.asarray(w0))
            lips.append(float(np.linalg.norm(g2 - g) / max(dw, 1e-9)))
        weights = sim.d_sizes / sim.d_sizes.sum()
        global_g = np.sum([w * g for w, g in zip(weights, grads)], axis=0)
        deltas = [float(np.linalg.norm(g - global_g)) for g in grads]
        return DataStats(np.asarray(sigmas), np.asarray(deltas),
                         np.maximum(np.asarray(lips), 0.1),
                         sim.d_tilde.astype(float))

    def train_round(self, sim: "Simulation", trained: List[int],
                    l_n: np.ndarray,
                    with_boundary: bool = False) -> Optional[np.ndarray]:
        """One round as the seed ran it: a Python loop over gateways and
        devices with per-device jitted split-SGD steps."""
        sc = sim.scenario
        models, weights = [], []
        for m in trained:
            gw = sim.gateways[m]
            l_splits = np.asarray([l_n[d.idx] for d in gw.devices])
            combined, gw_loss, w_m = gw.shop_floor_round(
                sim.plan, sim.params, sim.ds, l_splits,
                sc.k_iters, sc.lr, sim.rng)
            models.append(combined)
            weights.append(w_m)
            sim.losses[m] = gw_loss
        sim.bs.aggregate(models, np.asarray(weights))
        return None


# ---------------------------------------------------------------------------
# Simulation
# ---------------------------------------------------------------------------

PolicyLike = Union[str, object, None]


class _CheckpointWriter:
    """One daemon thread draining checkpoint write jobs in FIFO order.

    ``submit`` returns immediately; ``flush`` blocks until every submitted
    job has fully finished and re-raises the first exception any job hit,
    so callers get one crisp completion/failure point instead of silent
    data loss. Jobs must close over *snapshots* — the caller's state may
    mutate while the write is in flight.

    The thread is a daemon, so an atexit hook drains the queue at
    interpreter shutdown: a process that exits without ever calling
    ``flush`` still lands every submitted checkpoint on disk (a swallowed
    background error is surfaced as a warning there, the best that can be
    done that late).
    """

    def __init__(self):
        self._q: "queue.Queue" = queue.Queue()
        self._err: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="ckpt-writer")
        self._thread.start()
        atexit.register(self._drain_at_exit)

    def _drain_at_exit(self) -> None:
        self._q.join()
        if self._err is not None:
            warnings.warn(f"background checkpoint write failed and was "
                          f"never flush()ed: {self._err!r}")

    def _loop(self):
        while True:
            job = self._q.get()
            try:
                job()
            except BaseException as e:      # surfaced at the next flush()
                if self._err is None:
                    self._err = e
            finally:
                self._q.task_done()

    def submit(self, job) -> None:
        self._q.put(job)

    def flush(self) -> None:
        self._q.join()
        err, self._err = self._err, None
        if err is not None:
            raise err


class Simulation:
    """Composable FL simulation over a :class:`Scenario`.

    State is resolved once at construction (topology, dataset, model, layer
    cost model, per-device statistics); ``rounds()`` then streams
    :class:`RoundRecord` telemetry one round at a time.
    """

    def __init__(self, scenario: Scenario,
                 _stats: Optional[DataStats] = None):
        self.scenario = sc = scenario
        self.engine: Engine = make_engine(sc.engine)
        if sc.dtype not in cohort_lib.COMPUTE_DTYPES:
            raise ValueError(
                f"Scenario.dtype={sc.dtype!r}: expected one of "
                f"{sorted(cohort_lib.COMPUTE_DTYPES)}")
        if sc.dtype not in self.engine.supported_dtypes:
            raise ValueError(
                f"engine {sc.engine!r} supports dtypes "
                f"{self.engine.supported_dtypes}, not {sc.dtype!r}")
        if sc.data_plane not in ("host", "traced"):
            raise ValueError(
                f"Scenario.data_plane={sc.data_plane!r}: expected 'host' "
                "or 'traced'")
        if sc.data_plane == "traced" and \
                not self.engine.supports_traced_data:
            raise ValueError(
                f"engine {sc.engine!r} samples batches host-side: it "
                "cannot honor data_plane='traced'; use a cohort engine")
        if sc.buffer_k is not None and sc.buffer_k < 1:
            raise ValueError(f"Scenario.buffer_k must be >= 1 or None, "
                             f"got {sc.buffer_k}")
        self.faults = FaultModel.from_scenario(sc)
        if ((self.faults.active or sc.buffer_k is not None)
                and not self.engine.supports_faults):
            raise ValueError(
                f"engine {sc.engine!r} is synchronous: it cannot honor "
                f"fault axes (churn/dropout/stragglers) or buffer_k; use "
                f"engine='async'")
        self.net = Network(sc.net, np.random.default_rng(sc.seed))
        self.rng = np.random.default_rng(sc.seed + 1)
        ncfg = self.net.cfg

        # local dataset sizes D_n ~ U(0, 2000]; training batch D~_n = alpha*D_n
        self.d_sizes = np.maximum(
            (self.rng.uniform(0, sc.max_dataset, ncfg.n_devices)).astype(int),
            40)
        self.d_tilde = np.maximum((sc.alpha * self.d_sizes).astype(int), 4)

        # model resolved through the registry + layer-level costs (Table II);
        # built *before* the dataset so its input_kind can pick the data
        # path (consumes only the jax PRNG — the numpy byte stream the
        # image dataset replays is untouched).
        with obs.span("repro.setup.weights"):
            key = jax.random.PRNGKey(sc.seed)
            self.plan, params, self.layers = model_registry.build_fl_model(
                sc.model, key, sc)
            self.bs = BaseStation(self.plan, params)

        with obs.span("repro.setup.data"):
            if self.plan.input_kind == "tokens":
                # token models: per-device Markov-chain corpora whose
                # transition tables play the role of the class mixture
                # (chi-mixed)
                self.ds = make_token_fl_dataset(
                    ncfg.n_devices, self.d_sizes, vocab=self.plan.classes,
                    seq_len=sc.seq_len, chi=sc.chi, seed=sc.seed)
            else:
                # non-IID classes: gateway 0's devices see the widest
                # variety (paper Sec. VII-B: "the 1-th gateway ... a wider
                # variety")
                q = np.zeros(ncfg.n_devices, dtype=int)
                for n in range(ncfg.n_devices):
                    gw = self.net.assign[n]
                    q[n] = sc.classes if gw == 0 \
                        else int(self.rng.integers(1, 4))
                self.ds = make_fl_dataset(ncfg.n_devices, self.d_sizes, q,
                                          chi=sc.chi, classes=sc.classes,
                                          seed=sc.seed)

        o = cm.flops_vector(self.layers)
        g = cm.mem_vector(self.layers, batch=int(self.d_tilde.max()))
        # the model upload is priced at the scenario's compression level:
        # Workload.gamma feeds every uplink/downlink delay and energy term in
        # the DDSRA solvers, so quantized uploads shift the whole schedule.
        self.workload = Workload(
            o, g, cm.upload_bytes(self.layers, sc.effective_upload_bits),
            sc.k_iters, self.d_tilde.astype(float))

        self.gateways = [
            Gateway(m, [Device(int(n), m, int(self.d_sizes[n]),
                               int(self.d_tilde[n]))
                        for n in self.net.devices_of(m)])
            for m in range(ncfg.n_gateways)]

        # the scheduler can select at most n_channels gateways per round
        # (C2/C3), so this many slots always fit every round's participants;
        # packing into them skips compute for absent devices at fixed shapes.
        per_gw = int(np.bincount(self.net.assign,
                                 minlength=ncfg.n_gateways).max())
        self.cohort_capacity = min(ncfg.n_devices, ncfg.n_channels * per_gw)
        self._layouts: Dict = {}      # (capacity, tiers, shards) -> layout

        # ``_stats`` (resume fast path) skips the estimation pass entirely —
        # callers providing it are responsible for also restoring the batch
        # RNG state, since no estimation draws are consumed.
        with obs.span("repro.setup.stats") as stats_span:
            self.stats = _stats if _stats is not None \
                else self.engine.estimate_stats(self, params)
        self.stats_seconds = stats_span.seconds
        self.phi = divergence_bound(self.stats, self.net.assign,
                                    sc.lr, sc.k_iters)
        self.gamma = participation_rates(self.phi, ncfg.n_channels)

        # snapshots for reset(): fresh-Simulation replay of all three streams
        self._init_params = params
        self._rng_state0 = self.rng.bit_generator.state
        self._net_rng_state0 = self.net.rng.bit_generator.state

        self._policy = None
        self.run_seed = sc.seed   # threaded into stochastic policies
        self._ckpt_writer: Optional[_CheckpointWriter] = None
        self.restart()

    # -- state ----------------------------------------------------------

    @property
    def params(self):
        return self.bs.params

    @params.setter
    def params(self, value):
        self.bs.params = value

    @property
    def data_key(self):
        """Root key of the traced data plane's counter-based batch draws
        (``repro.fl.data.traced_batch_indices``). Derived from the run
        seed — one step past the batch-RNG seed (``seed + 1``) and the
        channel-RNG seed (``seed``) — so ``reset(seed)`` and checkpoint
        resume re-derive it with no extra state to save."""
        return jax.random.PRNGKey(self.run_seed + 2)

    def restart(self) -> None:
        """Reset the *run* state (round counter, queues, losses, delay) while
        keeping params and RNG streams — what a fresh ``run()`` call does.
        Engine-internal run state (the async engine's in-flight heap and
        staleness buffer) is discarded too: the clock rewinds, so updates
        from the previous run must not land in the next one."""
        ncfg = self.net.cfg
        self.t = 0
        self.queues = np.zeros(ncfg.n_gateways)
        self.losses = np.full(ncfg.n_gateways, self.plan.init_loss)
        self.delay_sum = 0.0
        # cumulative padded-vs-real sample counts (cohort engines fill this)
        self.padding_stats = {"real_samples": 0.0, "padded_samples": 0.0}
        self._policy = None
        self._policy_unresumable = False
        self.engine.reset(self)

    def reset(self, seed: Optional[int] = None) -> "Simulation":
        """Full reset for fair multi-policy sweeps.

        Restores the model parameters, the batch-sampling RNG **and** the
        network channel-state RNG together, so every policy run after a
        ``reset()`` faces the identical ChannelState sequence, data draws and
        initialization. With ``seed=None`` this replays a fresh
        ``Simulation(scenario)`` exactly; an explicit ``seed`` re-seeds the
        run-level streams — params init, batch RNG, channel RNG and the
        seed threaded into stochastic policies — while the scenario-level
        structure (topology, deployment, dataset) stays fixed.
        """
        with obs.span("repro.reset"):
            if seed is None or seed == self.scenario.seed:
                self.bs.params = self._init_params
                self.rng.bit_generator.state = self._rng_state0
                self.net.rng.bit_generator.state = self._net_rng_state0
            else:
                key = jax.random.PRNGKey(seed)
                _, self.bs.params, _ = model_registry.build_fl_model(
                    self.scenario.model, key, self.scenario)
                self.rng = np.random.default_rng(seed + 1)
                self.net.rng = np.random.default_rng(seed)
            self.run_seed = self.scenario.seed if seed is None else seed
            self.restart()
        return self

    # -- policies --------------------------------------------------------

    def _resolve_policy(self, policy: PolicyLike):
        if policy is None:
            policy = self.scenario.policy
        if isinstance(policy, str):
            return make_policy(policy, seed=self.run_seed)
        return policy

    # -- the round loop --------------------------------------------------

    def _ensure_policy(self, policy: PolicyLike):
        """Resolve/install the active policy (override > restored >
        scenario default), refusing to silently swap out an unresumable
        checkpointed custom policy."""
        if policy is not None:
            self._policy = self._resolve_policy(policy)
            self._policy_unresumable = False
        elif self._policy is None:
            if self._policy_unresumable:
                raise ValueError(
                    "this checkpoint was taken with an unregistered custom "
                    "policy; pass that policy explicitly to rounds()/run() "
                    "to continue")
            self._policy = self._resolve_policy(None)
        return self._policy

    def rounds(self, policy: PolicyLike = None, *,
               boundary: bool = False) -> Iterator[RoundRecord]:
        """Stream one RoundRecord per remaining round.

        ``policy`` (name or instance) overrides the scenario default; when
        resuming from a checkpoint the restored policy is kept unless a new
        one is passed. ``boundary=True`` adds per-device boundary-activation
        RMS telemetry to each record (one extra fused forward per round).
        """
        self._ensure_policy(policy)
        while self.t < self.scenario.rounds:
            yield self._step(self._policy, boundary)

    def _step(self, policy, boundary: bool) -> RoundRecord:
        sc = self.scenario
        ncfg = self.net.cfg
        t = self.t
        st = self.net.draw()
        prev_queues = self.queues
        ctx = RoundContext(t, self.workload, self.net, st, self.queues,
                           self.gamma, sc.v, losses=self.losses.copy(),
                           inflight=self.engine.inflight_counts(self))
        dec: RoundDecision = policy.schedule(ctx)
        self.queues = dec.queues

        # resolve the schedule into trained gateways + per-device cuts
        trained, l_n, gw_delay, failures = resolve_decision(
            dec, self.gateways, ncfg.n_devices)

        out = self.engine.run_round(self, dec, trained, l_n, gw_delay,
                                    boundary=boundary)
        # Asynchronous engines report *realized* participation: updates that
        # actually landed at the server this round (late arrivals included,
        # churned ones excluded). When it diverges from the schedule, redo
        # Eq. (14) from the pre-decision queues with the realized indicator;
        # when it matches (every synchronous engine, and fault-free async
        # rounds) keep the scheduler's own queues bit-identically.
        if out.realized is not None and \
                not np.array_equal(out.realized, dec.selected):
            self.queues = update_queues_realized(prev_queues, out.realized,
                                                 self.gamma)
        self.delay_sum += out.delay
        self.t = t + 1

        acc = None
        if (t + 1) % sc.eval_every == 0 or t == sc.rounds - 1:
            acc = self.plan.accuracy(self.params,
                                     self.ds.x_test, self.ds.y_test)
        return RoundRecord(t=t, selected=dec.selected.copy(),
                           trained=trained, l_n=l_n, delay=out.delay,
                           cum_delay=self.delay_sum,
                           queues=self.queues.copy(),
                           losses=self.losses.copy(), failures=failures,
                           boundary_rms=out.boundary_rms, accuracy=acc,
                           aggregations=out.aggregations,
                           staleness_mean=out.staleness_mean,
                           staleness_max=out.staleness_max,
                           stale_discarded=out.stale_discarded,
                           dropped_devices=out.dropped_devices,
                           lost_devices=out.lost_devices,
                           straggler_devices=out.straggler_devices,
                           buffer_fill=out.buffer_fill,
                           inflight=out.inflight)

    def run(self, policy: PolicyLike = None, *,
            boundary: bool = False) -> FLResult:
        """Consume the full round loop into an :class:`FLResult`.

        Restarts the run state (round counter, queues, losses) but keeps the
        current params/RNG streams, matching the historical ``FLTrainer.run``
        semantics; call :meth:`reset` first for a from-scratch fair run.
        """
        self.restart()
        records = list(self.rounds(policy, boundary=boundary))
        self.flush()     # any per-round save() has fully landed on return
        return self.result_of(records)

    # -- the fused round loop (repro.fl.fused_sim) -----------------------

    def fused_rounds(self, policy: PolicyLike = None, *,
                     rounds: Optional[int] = None) -> List[RoundRecord]:
        """Run the remaining rounds as fused scans instead of the stepwise
        loop: one compiled decide program (traced policies) or a host
        decide loop, plus ONE compiled training program scanning all
        rounds — same :class:`RoundRecord` stream, same end state
        (bit-identical queues/RNG, params to 1e-5; the parity matrix in
        ``tests/test_fused_sim.py`` pins this). ``rounds`` caps how many
        rounds this call advances (default: all remaining). Intermediate
        ``eval_every`` accuracies are not computed inside the scan — only
        a final-round eval is reported (records keep ``accuracy=None``
        elsewhere).
        """
        from repro.fl import fused_sim
        return fused_sim.fused_rounds(self, self._ensure_policy(policy),
                                      rounds=rounds)

    def run_fused(self, policy: PolicyLike = None) -> FLResult:
        """:meth:`run`, but through :meth:`fused_rounds` — restart the run
        state, execute every round in fused scans, fold the records into
        an :class:`FLResult`."""
        self.restart()
        records = self.fused_rounds(policy)
        self.flush()
        return self.result_of(records)

    def sweep(self, v_values, seeds=None, *,
              rounds: Optional[int] = None, policies=None):
        """Run a scheduling sweep as a single compiled program.

        Draws each seed's channel trajectory host-side under the
        ``reset(seed)`` fairness contract (so sweep lane (s, v) sees
        exactly the ChannelStates a stepwise ``reset(s)`` run at that V
        would), stacks them, and fuses the grid: with ``policies=None``
        runs ``repro.core.ddsra_jax.DDSRAPlan.sweep_states`` — vmap over
        seeds, vmap over V (lanes share a seed's draws), ``lax.scan`` over
        rounds — which requires a traced-decide scenario policy
        (``ddsra_jax``). With ``policies=[...]`` (traced-decide policy
        names) a one-hot policy axis joins the grid and the whole
        policies x seeds x V sweep runs as ONE program
        (``repro.core.policy_sweep`` — the Figs. 4-6 comparison). Returns
        a ``repro.fl.fused_sim.SweepResult``.
        """
        from repro.fl import fused_sim
        return fused_sim.sweep(self, v_values, seeds=seeds, rounds=rounds,
                               policies=policies)

    def result_of(self, records: List[RoundRecord]) -> FLResult:
        """Fold a list of streamed RoundRecords into an :class:`FLResult`."""
        acc = [r.accuracy for r in records if r.accuracy is not None]
        acc_rounds = [r.t + 1 for r in records if r.accuracy is not None]
        return FLResult(
            accuracy=acc, acc_rounds=acc_rounds,
            cum_delay=[r.cum_delay for r in records],
            participation=np.asarray([r.selected for r in records]),
            gamma_targets=self.gamma,
            losses=[float(np.mean(r.losses)) for r in records],
            phi=self.phi,
            failures=sum(r.failures for r in records))

    # -- statistics ------------------------------------------------------

    def estimate_stats(self, params=None,
                       engine: Optional[str] = None) -> DataStats:
        """Online estimators for sigma_n, delta_n, L_n (paper Sec. VII-A)."""
        eng = self.engine if engine is None else make_engine(engine)
        return eng.estimate_stats(
            self, self.params if params is None else params)

    # -- checkpointing ---------------------------------------------------

    def save(self, path, keep_last: Optional[int] = None, *,
             block: bool = False) -> pathlib.Path:
        """Checkpoint params + full run state at round ``self.t``.

        Non-blocking by default: the run state is *snapshotted* on the
        calling thread (cheap — references to immutable jax arrays plus
        small host copies), then a single background writer thread performs
        the actual serialization and atomic renames, so per-round
        checkpointing no longer stalls the round loop on disk I/O. The
        returned path may not exist yet — call :meth:`flush` before reading
        it (or pass ``block=True`` to write inline). Every file lands via
        tmp + ``os.replace``, so a concurrent :meth:`resume` only ever sees
        absent or complete checkpoints, never partial ones.

        ``keep_last`` (default: ``Scenario.keep_last``) rotates the
        checkpoint directory: after this save only the newest ``keep_last``
        round checkpoints survive — the ``step_*.npz`` param files (GC'd by
        ``store.save_pytree``), their ``sim_*.json`` run-state manifests and
        any ``engine_*`` side-cars alike — so per-round saving on long runs
        uses bounded disk.
        """
        if keep_last is None:
            keep_last = self.scenario.keep_last
        path = pathlib.Path(path)
        step = self.t
        params = self.params                       # immutable jax pytree
        pol = None
        if self._policy is not None:
            name = getattr(self._policy, "name", None)
            # only registered names can be reconstructed at resume time; a
            # custom instance is recorded as such so resume can refuse to
            # silently swap in the scenario default mid-experiment.
            pol = {"name": name if name in POLICIES else None,
                   "state": policy_state(self._policy)}
        eng = self.engine.state_dict(self)
        eng_meta, eng_arrays = eng if eng is not None else (None, None)
        state = {
            "scenario": self.scenario.to_json(),
            "t": step,
            "run_seed": self.run_seed,
            "queues": self.queues.tolist(),
            "losses": self.losses.tolist(),
            "delay_sum": self.delay_sum,
            "rng": self.rng.bit_generator.state,
            "net_rng": self.net.rng.bit_generator.state,
            # stats with exact dtypes: phi/gamma recomputation at resume is
            # then bit-identical, and the estimation pass can be skipped.
            "stats": {f.name: _arr_to_json(getattr(self.stats, f.name))
                      for f in dataclasses.fields(self.stats)},
            "policy": pol,
            "engine": eng_meta,
        }
        payload = json.dumps(state).encode()       # serialized pre-submit
        fname = path / f"sim_{step:08d}.json"

        def job():
            store.save_pytree(path, params, step=step, keep_last=keep_last)
            if eng_arrays is not None:
                store.save_pytree(path, eng_arrays, step=step,
                                  prefix="engine")
            store.atomic_write_bytes(fname, lambda f: f.write(payload))
            if keep_last is not None:
                kept = set(store.all_steps(path))  # post-GC param ckpts
                for fam in ("sim", "engine"):
                    for f in path.glob(f"{fam}_*.*"):
                        m = re.match(rf"{fam}_(\d+)\.(json|npz)", f.name)
                        if m and int(m.group(1)) not in kept:
                            f.unlink(missing_ok=True)

        if block:
            self.flush()      # keep FIFO order with pending async saves
            job()
        else:
            if self._ckpt_writer is None:
                self._ckpt_writer = _CheckpointWriter()
            self._ckpt_writer.submit(job)
        return fname

    def flush(self) -> None:
        """Block until every pending non-blocking :meth:`save` has fully
        landed on disk; re-raises the first error any background write hit.
        A no-op when nothing is pending."""
        if self._ckpt_writer is not None:
            self._ckpt_writer.flush()

    @classmethod
    def resume(cls, path) -> "Simulation":
        """Rebuild a Simulation from the latest checkpoint in ``path``.

        The scenario is re-resolved deterministically (topology, dataset;
        the per-device statistics come straight from the manifest, skipping
        the estimation pass), then params and every RNG/queue/loss/policy
        stream are restored, so the continued round loop is bit-identical
        to an uninterrupted run.
        """
        path = pathlib.Path(path)
        step = store.latest_step(path)
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {path}")
        state = json.loads((path / f"sim_{step:08d}.json").read_text())
        stats = None
        if "stats" in state:
            stats = DataStats(**{k: _arr_from_json(v)
                                 for k, v in state["stats"].items()})
        sim = cls(Scenario.from_json(state["scenario"]), _stats=stats)
        sim.params = store.load_pytree(path / f"step_{step:08d}.npz",
                                       like=sim.params)
        sim.t = state["t"]
        sim.run_seed = state.get("run_seed", sim.scenario.seed)
        sim.queues = np.asarray(state["queues"])
        sim.losses = np.asarray(state["losses"])
        sim.delay_sum = state["delay_sum"]
        sim.rng.bit_generator.state = state["rng"]
        sim.net.rng.bit_generator.state = state["net_rng"]
        pol = state.get("policy")
        if pol:
            if pol.get("name"):
                sim._policy = make_policy(pol["name"], seed=sim.run_seed)
                set_policy_state(sim._policy, pol.get("state"))
            else:
                sim._policy_unresumable = True
        eng_meta = state.get("engine")
        if eng_meta is not None:
            sim.engine.load_state_dict(sim, eng_meta, path, step)
        return sim


def _arr_to_json(a: np.ndarray) -> dict:
    a = np.asarray(a)
    return {"data": a.tolist(), "dtype": str(a.dtype)}


def _arr_from_json(d: dict) -> np.ndarray:
    return np.asarray(d["data"], dtype=d["dtype"])


def _unflatten_like(flat: np.ndarray, tree):
    """Split a flat vector back into leaves shaped like ``tree``."""
    leaves = jax.tree.leaves(tree)
    out, i = [], 0
    for leaf in leaves:
        n = leaf.size
        out.append(np.asarray(flat[i:i + n]).reshape(leaf.shape)
                   .astype(leaf.dtype))
        i += n
    return out


# Registers ShardedCohortEngine under "sharded" and AsyncCohortEngine under
# "async" in ENGINES. Must stay at the bottom: both modules subclass
# CohortEngine from this module.
import repro.fl.shard  # noqa: E402,F401
import repro.fl.async_engine  # noqa: E402,F401
