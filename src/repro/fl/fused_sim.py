"""Whole-simulation fusion: the round loop as compiled scans.

The stepwise :meth:`repro.fl.sim.Simulation.rounds` loop crosses the host
every round — repackage the jitted DDSRA solve into a
:class:`~repro.core.ddsra.RoundDecision`, resolve it in Python, launch one
fused training program, sync the loss. This module runs the same
simulate → decide → train trajectory as (up to) two compiled programs plus
one host replay pass:

* **Fused decide** — for traced policies the whole decide trajectory is
  ONE program: ``lax.scan`` of the traced round over the stacked channel
  states, resolving each round into the pytree-typed
  :class:`~repro.core.ddsra_jax.RoundDecisionT` *inside* the scan.
  ``ddsra_jax`` scans the full Algorithm 1 solve
  (:meth:`repro.core.ddsra_jax.DDSRAPlan.decide_scan`); the
  fixed-resource ``round_robin``/``random`` baselines scan the
  feasibility/delay evaluation of ``repro.core.baseline_jax`` with their
  gateway picks fed in as data. Remaining host policies (the numpy
  oracle, loss/delay-driven) decide via a host loop instead — still
  exact, just not fused.
* **Batch replay** — :meth:`CohortEngine._pack_round` runs per round on the
  host, consuming ``sim.rng`` with exactly the draws the stepwise loop
  would make (the packing contract), so the fused path is RNG-bit-identical
  to stepwise. The packed per-round tensors stack into per-tier arrays
  with a leading round axis. Under ``Scenario.data_plane="traced"`` this
  phase shrinks to *metadata only* (:func:`_pack_rounds_traced`): batches
  are gathered in-scan from device-resident shard stacks via counter-based
  jax draws (``repro.fl.data.traced_batch_indices``), so no per-round
  sample copies cross the host at all.
* **Fused train** — ONE program scans the fused cohort round over all
  rounds (``repro.fl.cohort.train_scan`` / ``train_scan_traced``; the
  sharded engine's twins wrap the scan in ``shard_map``), threading
  (params, losses) as the carry and the stacked decision tensors straight
  from the decide scan. ``eval_every`` accuracy snapshots run
  ``lax.cond``-gated *inside* the scan and cross back as per-round hit
  counts. The precision contract survives inside the pipeline: the decide
  program runs x64 (``jax.enable_x64(True)``), the train program
  f32/bf16.

Why decide and train can be phase-separated at all: every fusable policy's
decisions depend only on channel draws and the queue recursion — never on
training outputs. The one feedback-coupled policy (``loss_driven``,
``reads_losses = True``) is refused. Channel streams stay exact because
states are pre-drawn host-side from the same ``net.rng`` before the batch
replay touches ``sim.rng`` — two independent generators, each consumed in
stepwise order.

Telemetry crosses back to the host once, after the scans, as a stacked
:class:`RoundTelemetry` pytree (one leaf per :class:`RoundRecord` field,
leading round axis) and is streamed into the familiar per-round records by
:meth:`RoundTelemetry.to_records`. Parity with the stepwise loop —
bit-identical queues and RNG streams, params at 1e-5 — is pinned across
{cohort, sharded} x {ddsra_jax, round_robin, delay_driven} x {f32, bf16}
x {host, traced} data planes in ``tests/test_fused_sim.py``.
"""
from __future__ import annotations

import dataclasses
from typing import List, NamedTuple, Optional, Sequence

import jax
import numpy as np

from repro import obs
from repro.core.network import ChannelState, stack_states
from repro.core.schedulers import RoundContext, make_policy
from repro.fl.sim import (RoundRecord, Simulation, resolve_decision)


class RoundTelemetry(NamedTuple):
    """Stacked per-round telemetry as a pytree: one leaf per (array-like)
    :class:`~repro.fl.sim.RoundRecord` field, every leaf carrying a leading
    ``(rounds,)`` axis.

    This is the side-channel the fused loop streams telemetry through:
    scan outputs land here as stacked device arrays, cross the host
    boundary once, and fan back out into per-round records via
    :meth:`to_records`. ``boundary_rms`` and ``accuracy`` are not leaves —
    they are optional per-round host artifacts (``None`` inside the fused
    loop) and would force ragged shapes.

    ``flatten -> unflatten`` is the identity (NamedTuples are JAX pytrees)
    and :meth:`from_records` / :meth:`to_records` round-trip exactly —
    both pinned by the Hypothesis property test in
    ``tests/test_fused_sim.py``.
    """
    t: np.ndarray                  # (T,) int
    selected: np.ndarray           # (T, M) bool
    trained: np.ndarray            # (T, M) bool (records carry id lists)
    l_n: np.ndarray                # (T, N) int
    delay: np.ndarray              # (T,) float64
    cum_delay: np.ndarray          # (T,) float64
    queues: np.ndarray             # (T, M) float64
    losses: np.ndarray             # (T, M) float64
    failures: np.ndarray           # (T,) int
    aggregations: np.ndarray       # (T,) int
    staleness_mean: np.ndarray     # (T,) float64 (0.0 when no aggregation)
    staleness_max: np.ndarray      # (T,) int
    stale_discarded: np.ndarray    # (T,) int
    dropped_devices: np.ndarray    # (T,) int
    lost_devices: np.ndarray       # (T,) int
    straggler_devices: np.ndarray  # (T,) int
    buffer_fill: np.ndarray        # (T,) int
    inflight: np.ndarray           # (T,) int

    @classmethod
    def from_records(cls, records: Sequence[RoundRecord]
                     ) -> "RoundTelemetry":
        """Stack per-round records into one pytree (trained id lists become
        the (T, M) bool mask; ``boundary_rms``/``accuracy`` are dropped)."""
        m_gw = len(records[0].queues)
        trained = np.zeros((len(records), m_gw), bool)
        for i, r in enumerate(records):
            trained[i, list(r.trained)] = True
        pick = {
            "t": (int, None), "selected": (bool, None),
            "l_n": (int, None), "delay": (np.float64, None),
            "cum_delay": (np.float64, None), "queues": (np.float64, None),
            "losses": (np.float64, None), "failures": (int, None),
            "aggregations": (int, None),
            "staleness_mean": (np.float64, None), "staleness_max": (int, None),
            "stale_discarded": (int, None), "dropped_devices": (int, None),
            "lost_devices": (int, None), "straggler_devices": (int, None),
            "buffer_fill": (int, None), "inflight": (int, None)}
        cols = {k: np.asarray([getattr(r, k) for r in records], dtype=dt)
                for k, (dt, _) in pick.items()}
        return cls(trained=trained, **cols)

    def to_records(self) -> List[RoundRecord]:
        """Fan the stacked leaves back out into per-round records (host
        streaming after the scan). Every value is concretized to host
        numpy/Python — a traced leaf here would be a leak, which the
        property test rejects."""
        out = []
        for i in range(len(np.asarray(self.t))):
            out.append(RoundRecord(
                t=int(self.t[i]),
                selected=np.asarray(self.selected[i]).copy(),
                trained=[int(m) for m in np.where(self.trained[i])[0]],
                l_n=np.asarray(self.l_n[i]).copy(),
                delay=float(self.delay[i]),
                cum_delay=float(self.cum_delay[i]),
                queues=np.asarray(self.queues[i], np.float64).copy(),
                losses=np.asarray(self.losses[i], np.float64).copy(),
                failures=int(self.failures[i]),
                aggregations=int(self.aggregations[i]),
                staleness_mean=float(self.staleness_mean[i]),
                staleness_max=int(self.staleness_max[i]),
                stale_discarded=int(self.stale_discarded[i]),
                dropped_devices=int(self.dropped_devices[i]),
                lost_devices=int(self.lost_devices[i]),
                straggler_devices=int(self.straggler_devices[i]),
                buffer_fill=int(self.buffer_fill[i]),
                inflight=int(self.inflight[i])))
        return out


@dataclasses.dataclass
class SweepResult:
    """Outcome of a scheduling sweep run as one compiled program
    (:meth:`repro.fl.sim.Simulation.sweep`).

    Single-policy (``policies is None``): row (s, v) matches a stepwise
    ``reset(seeds[s])`` run of the same scenario at ``v_values[v]``
    row-for-row — ``taus[s, v, t]`` is round t's realized delay,
    ``selected``/``queues`` its participation and post-update queue state
    (the seed-determinism test pins this, cross-process). Arrays carry
    (S, V, T[, M]) axes.

    Multi-policy (``policies`` a list of traced-decide policy names): the
    whole policies x seeds x V grid ran as ONE program
    (``repro.core.policy_sweep``) and every array gains a leading policy
    axis — (P, S, V, T[, M]); row (p, s, v) matches a stepwise
    ``reset(seeds[s])`` run with ``Scenario.policy=policies[p]`` at
    ``v_values[v]``. Fixed-resource baseline lanes ignore V, so their
    rows repeat across the V axis (the flat curves of Figs. 4-6)."""
    seeds: List[int]
    v_values: List[float]
    taus: np.ndarray       # ([P,] S, V, T)
    selected: np.ndarray   # ([P,] S, V, T, M) bool
    queues: np.ndarray     # ([P,] S, V, T, M)
    policies: Optional[List[str]] = None


# ---------------------------------------------------------------------------
# phase A: decide
# ---------------------------------------------------------------------------


def _check_fusable(sim: Simulation, policy) -> None:
    if getattr(policy, "reads_losses", False):
        raise ValueError(
            f"policy {getattr(policy, 'name', policy)!r} reads training "
            "losses (reads_losses=True): decide and train cannot be "
            "phase-separated; use Simulation.rounds()")
    if not getattr(sim.engine, "supports_fused", False):
        # surface the engine's own refusal (async explains its buffer state)
        sim.engine.fused_train(sim, None, None, None, None, None, None,
                               None, None, None)


def _decide(sim: Simulation, policy, states: List[ChannelState], t0: int):
    """Run the decide trajectory over pre-drawn channel states.

    Traced policies (``traced_decide``) go through
    :meth:`DDSRAPlan.decide_scan` — one compiled program for all rounds;
    everything else replays the stepwise host loop (same ``schedule(ctx)``
    calls, same queue handoff, so queues/policy-RNG stay bit-identical).
    Returns host numpy arrays: (selected (T, M), trained (T, M),
    l_n (T, N), delay (T,), failures (T,), queues (T, M)).
    """
    sc = sim.scenario
    n_dev = sim.net.cfg.n_devices
    if getattr(policy, "traced_decide", False):
        with obs.span("repro.fused.decide.plan", block=t0):
            plan = policy.plan_for(sim.workload, sim.net)
        kwargs = {}
        if hasattr(policy, "traced_chosen"):
            # fixed-resource baselines: gateway picks are data — drawn /
            # computed host-side (preserving the stepwise policy-RNG
            # stream) and fed to the scan as its round axis. delay_driven
            # returns None (its pick depends on the round's channel draws)
            # and decide_scan computes the greedy pick in-scan instead.
            chosen = policy.traced_chosen(t0, len(states), sim.net)
            if chosen is not None:
                kwargs["chosen"] = chosen
        with obs.span("repro.fused.decide.dispatch", block=t0):
            dec = plan.decide_scan(stack_states(states), sim.queues,
                                   sim.gamma, sc.v, **kwargs)
        with obs.span("repro.fused.decide.wait", block=t0):
            selected = np.asarray(dec.selected)
        # the other leaves are ready: each conversion is one copy
        with obs.span("repro.fused.decide.fetch", block=t0):
            return (selected, np.asarray(dec.trained),
                    np.asarray(dec.l_dev).astype(int),
                    np.asarray(dec.delay, np.float64),
                    np.asarray(dec.failures).astype(int),
                    np.asarray(dec.queues, np.float64))

    m_gw = sim.net.cfg.n_gateways
    T = len(states)
    selected = np.zeros((T, m_gw), bool)
    trained_mask = np.zeros((T, m_gw), bool)
    l_rounds = np.zeros((T, n_dev), int)
    delay = np.zeros(T)
    failures = np.zeros(T, int)
    queues_out = np.zeros((T, m_gw))
    queues = sim.queues
    for k, st in enumerate(states):
        ctx = RoundContext(t0 + k, sim.workload, sim.net, st, queues,
                           sim.gamma, sc.v, losses=sim.losses.copy(),
                           inflight=None)
        dec = policy.schedule(ctx)
        queues = dec.queues
        trained, l_n, gw_delay, fails = resolve_decision(
            dec, sim.gateways, n_dev)
        selected[k] = dec.selected
        trained_mask[k, trained] = True
        l_rounds[k] = l_n
        delay[k] = max(gw_delay.values(), default=0.0)
        failures[k] = fails
        queues_out[k] = queues
    return selected, trained_mask, l_rounds, delay, failures, queues_out


# ---------------------------------------------------------------------------
# phase B: host batch replay (exact RNG parity with the stepwise loop)
# ---------------------------------------------------------------------------


def _replay_batches(sim: Simulation, trained_mask: np.ndarray,
                    l_rounds: np.ndarray):
    """Pack every round through the engine's ``_pack_round`` — consuming
    ``sim.rng`` with exactly the stepwise draws — and stack the packed
    tensors into per-tier arrays with a leading round axis.

    Returns per-tier tuples (xs, ys, masks, ls, ws, gws): tier k carries
    ``(T, S_k, ...)`` arrays, ready for the fused training scan. Rounds
    where nobody trains still pack (zero draws, zero masks/weights), so
    shapes stay fixed. Each packed tensor is written straight into row k
    of a preallocated stacked buffer — the replay pays exactly one copy
    per tensor, the same as the stepwise loop's per-round conversion.
    """
    T = trained_mask.shape[0]
    layout0 = None
    stacked = None
    for k in range(T):
        trained = [int(m) for m in np.where(trained_mask[k])[0]]
        _, batch, layout, l_slot, w_slot, slot_gw = \
            sim.engine._pack_round(sim, trained, l_rounds[k])
        if layout0 is None:
            layout0 = layout
        elif layout is not layout0:
            raise RuntimeError(
                "cohort layout changed across rounds (capacity fallback); "
                "the fused scan needs fixed shapes — use "
                "Simulation.rounds()")
        if trained:  # stepwise accounting only touches training rounds
            sim.padding_stats["real_samples"] += float(
                sum(t.mask.sum() for t in batch.tiers))
            sim.padding_stats["padded_samples"] += float(
                layout.padded_samples)
        sizes = tuple(t.x.shape[0] for t in batch.tiers)
        if stacked is None:  # round 0 fixes every tier's shape
            stacked = (
                tuple(np.empty((T,) + t.x.shape, t.x.dtype)
                      for t in batch.tiers),
                tuple(np.empty((T,) + t.y.shape, t.y.dtype)
                      for t in batch.tiers),
                tuple(np.empty((T,) + t.mask.shape, np.float32)
                      for t in batch.tiers),
                tuple(np.empty((T, s), np.int32) for s in sizes),
                tuple(np.empty((T, s), np.float32) for s in sizes),
                tuple(np.empty((T, s) + np.shape(slot_gw)[1:], np.float32)
                      for s in sizes))
        xs, ys, masks, ls, ws, gws = stacked
        off = 0
        for i, t in enumerate(batch.tiers):
            xs[i][k] = t.x
            ys[i][k] = t.y
            masks[i][k] = t.mask
            ls[i][k] = l_slot[off:off + sizes[i]]
            ws[i][k] = w_slot[off:off + sizes[i]]
            gws[i][k] = slot_gw[off:off + sizes[i]]
            off += sizes[i]
    return stacked


def _pack_rounds_traced(sim: Simulation, trained_mask: np.ndarray,
                        l_rounds: np.ndarray):
    """The traced data plane's phase B: pack only round *metadata*.

    ``_pack_round_meta`` assigns slots without drawing a single sample —
    the fused scan gathers every batch in-program from the device-resident
    shard stacks via the counter-based draws — so this stacks a few int32/
    float32 per slot per round instead of ``(T, S_k, W_k, ...)`` sample
    buffers (the copy the host data plane pays per round disappears).

    Returns (slot_devs, ls, ws, gws, layout): per-tier tuples of
    ``(T, S_k[, M])`` arrays plus the (fixed) layout.
    """
    T = trained_mask.shape[0]
    layout0 = None
    stacked = None
    for k in range(T):
        trained = [int(m) for m in np.where(trained_mask[k])[0]]
        _, layout, slot_dev, l_slot, w_slot, slot_gw, real = \
            sim.engine._pack_round_meta(sim, trained, l_rounds[k])
        if layout0 is None:
            layout0 = layout
        elif layout is not layout0:
            raise RuntimeError(
                "cohort layout changed across rounds (capacity fallback); "
                "the fused scan needs fixed shapes — use "
                "Simulation.rounds()")
        if trained:  # stepwise accounting only touches training rounds
            sim.padding_stats["real_samples"] += float(real)
            sim.padding_stats["padded_samples"] += float(
                layout.padded_samples)
        sizes = tuple(layout.tier_slots)
        if stacked is None:
            stacked = (
                tuple(np.empty((T, s), np.int32) for s in sizes),
                tuple(np.empty((T, s), np.int32) for s in sizes),
                tuple(np.empty((T, s), np.float32) for s in sizes),
                tuple(np.empty((T, s) + np.shape(slot_gw)[1:], np.float32)
                      for s in sizes))
        sds, ls, ws, gws = stacked
        off = 0
        for i, s in enumerate(sizes):
            sds[i][k] = slot_dev[off:off + s]
            ls[i][k] = l_slot[off:off + s]
            ws[i][k] = w_slot[off:off + s]
            gws[i][k] = slot_gw[off:off + s]
            off += s
    return stacked + (layout0,)


# ---------------------------------------------------------------------------
# the fused round loop
# ---------------------------------------------------------------------------


def fused_rounds(sim: Simulation, policy, *,
                 rounds: Optional[int] = None) -> List[RoundRecord]:
    """Advance ``sim`` by (up to) ``rounds`` rounds through the fused
    pipeline (decide scan / host decide -> batch replay -> train scan) and
    return the same :class:`RoundRecord` stream the stepwise loop yields.

    End state (params, losses, queues, t, delay_sum, both RNG streams)
    matches stepwise exactly, so fused and stepwise blocks interleave — a
    checkpoint saved after a fused block resumes into either path.
    """
    sc = sim.scenario
    t0 = sim.t
    T = sc.rounds - t0 if rounds is None else min(rounds, sc.rounds - t0)
    if T <= 0:
        return []
    _check_fusable(sim, policy)
    with obs.span("repro.fused", block=t0, rounds=T):
        return _fused_block(sim, policy, t0, T)


def _fused_block(sim: Simulation, policy, t0: int, T: int
                 ) -> List[RoundRecord]:
    """The body of :func:`fused_rounds` for rounds ``t0 .. t0 + T - 1``,
    each host step in its own ``repro.fused.*`` span."""
    sc = sim.scenario
    # phase A: channel states from the SAME numpy stream as stepwise
    with obs.span("repro.fused.draw", block=t0):
        states = [sim.net.draw() for _ in range(T)]
    selected, trained_mask, l_rounds, delay, failures, queues = _decide(
        sim, policy, states, t0)

    # the stepwise eval_every schedule, evaluated lax.cond-gated *inside*
    # the train scan (repro.fl.cohort._eval_hits)
    ts = t0 + np.arange(T)
    eval_mask = ((ts + 1) % sc.eval_every == 0) | (ts == sc.rounds - 1)

    if sc.data_plane == "traced":
        # phases B+C, traced plane: pack metadata only; the scan gathers
        # every round's batches in-program via the counter-based draws
        with obs.span("repro.fused.pack", block=t0):
            slot_devs, ls, ws, gws, layout = _pack_rounds_traced(
                sim, trained_mask, l_rounds)
        with obs.span("repro.fused.train.dispatch", block=t0):
            params, losses, loss_hist, hits = \
                sim.engine.fused_train_traced(
                    sim, sim.params, sim.losses, ts, slot_devs, ls, ws, gws,
                    trained_mask, eval_mask, layout)
    else:
        # phase B: exact-RNG batch replay + stacking
        with obs.span("repro.fused.pack", block=t0):
            xs, ys, masks, ls, ws, gws = _replay_batches(sim, trained_mask,
                                                         l_rounds)

        # phase C: one training program for all rounds
        with obs.span("repro.fused.train.dispatch", block=t0):
            params, losses, loss_hist, hits = sim.engine.fused_train(
                sim, sim.params, sim.losses, xs, ys, masks, ls, ws, gws,
                trained_mask, eval_mask)
    with obs.span("repro.fused.train.wait", block=t0):
        loss_hist = np.asarray(loss_hist, np.float64)

    with obs.span("repro.fused.records", block=t0):
        cum = sim.delay_sum + np.cumsum(np.asarray(delay, np.float64))
        tel = RoundTelemetry(
            t=t0 + np.arange(T),
            selected=np.asarray(selected, bool),
            trained=np.asarray(trained_mask, bool),
            l_n=np.asarray(l_rounds, int),
            delay=np.asarray(delay, np.float64),
            cum_delay=cum,
            queues=np.asarray(queues, np.float64),
            losses=loss_hist,
            failures=np.asarray(failures, int),
            aggregations=np.asarray(trained_mask.any(axis=1), int),
            staleness_mean=np.zeros(T), staleness_max=np.zeros(T, int),
            stale_discarded=np.zeros(T, int), dropped_devices=np.zeros(T, int),
            lost_devices=np.zeros(T, int), straggler_devices=np.zeros(T, int),
            buffer_fill=np.zeros(T, int), inflight=np.zeros(T, int))
        records = tel.to_records()

        # commit the end state to the Simulation (stepwise-compatible)
        sim.params = params
        sim.losses = np.asarray(losses, np.float64)
        sim.queues = np.asarray(queues[-1], np.float64).copy()
        sim.t = t0 + T
        sim.delay_sum = float(cum[-1])

        # in-scan eval: hit counts crossed the host with the telemetry; turn
        # them into the stepwise loop's accuracy numbers (hits / test size —
        # exact, SplitModel.accuracy's chunking does not change integer hits)
        n_test = max(int(np.size(np.asarray(sim.ds.y_test))), 1)
        for r, h in zip(records, np.asarray(hits)):
            if h >= 0:
                r.accuracy = float(int(h)) / n_test
        return records


# ---------------------------------------------------------------------------
# seeds x V sweep
# ---------------------------------------------------------------------------


def _seed_states(sim: Simulation, seed: int, rounds: int
                 ) -> List[ChannelState]:
    """The channel trajectory a stepwise ``reset(seed)`` run would draw,
    without disturbing the live ``sim.net.rng`` stream (the reset(seed)
    fairness contract: scenario seed replays the pristine stream, any
    other seed reseeds it)."""
    if seed == sim.scenario.seed:
        rng = np.random.default_rng()
        rng.bit_generator.state = sim._net_rng_state0
    else:
        rng = np.random.default_rng(seed)
    saved = sim.net.rng
    sim.net.rng = rng
    try:
        return [sim.net.draw() for _ in range(rounds)]
    finally:
        sim.net.rng = saved


def sweep(sim: Simulation, v_values, seeds=None, *,
          rounds: Optional[int] = None,
          policies: Optional[List[str]] = None) -> SweepResult:
    """Run a scheduling sweep as ONE compiled program.

    ``policies=None`` (the classic V-sweep): resolves the scenario policy,
    which must be traced-decide (``ddsra_jax``); draws each seed's channel
    trajectory host-side under the reset(seed) contract; stacks them
    (S, T, ...) and hands off to :meth:`DDSRAPlan.sweep_states` —
    vmap(seeds) o vmap(V) o scan(rounds). All V lanes of a seed share its
    channel draws (fair-sweep contract).

    ``policies=[...]`` (the Figs. 4-6 grid): every named traced-decide
    policy becomes a lane of one ``lax.switch`` branch axis and the whole
    policies x seeds x V grid runs as a single XLA program
    (``repro.core.policy_sweep``). All policy lanes of a seed share its
    channel draws, and ``random``'s picks are pre-drawn per seed from the
    same policy-RNG stream a stepwise ``reset(seed)`` run would consume.
    """
    T = sim.scenario.rounds if rounds is None else rounds
    seeds = [sim.scenario.seed] if seeds is None else [int(s) for s in seeds]
    v_values = [float(v) for v in v_values]
    lanes = len(seeds) * len(v_values) * (1 if policies is None
                                          else len(policies))
    with obs.span("repro.sweep", lanes=lanes, rounds=T):
        return _sweep(sim, v_values, seeds, T, policies)


def _sweep(sim: Simulation, v_values, seeds: List[int], T: int,
           policies: Optional[List[str]]) -> SweepResult:
    """The body of :func:`sweep`, each host step in its own
    ``repro.sweep.*`` span (dispatch and wait are the plan's)."""
    if policies is not None:
        from repro.core import policy_sweep as ps
        from repro.core.baseline_jax import BaselinePlan
        bad = [p for p in policies if p not in ps.POLICY_KINDS]
        if bad:
            raise ValueError(
                f"policies {bad!r} cannot ride the fused sweep (host-loop "
                f"decide); traced-decide policies: "
                f"{sorted(ps.POLICY_KINDS)} — use Simulation.rounds() for "
                "the rest")
        with obs.span("repro.sweep.plan"):
            plan = BaselinePlan.build(sim.workload, sim.net)
        with obs.span("repro.sweep.draw"):
            per_seed = [stack_states(_seed_states(sim, s, T)) for s in seeds]
            stacked = jax.tree.map(lambda *a: np.stack(a), *per_seed)
        kinds = np.array([ps.POLICY_KINDS[p] for p in policies], np.int32)
        j_ch = sim.net.cfg.n_channels
        chosen = np.zeros((len(policies), len(seeds), T, j_ch), np.int32)
        with obs.span("repro.sweep.picks"):
            for pi, name in enumerate(policies):
                if ps.POLICY_KINDS[name] != 1:
                    continue
                for si, s in enumerate(seeds):
                    # fresh per-seed policy instance == the stepwise
                    # reset(seed) contract (make_policy reseeds from
                    # run_seed)
                    pol = make_policy(name, seed=s)
                    chosen[pi, si] = pol.traced_chosen(0, T, sim.net)
        taus, sel, queues = ps.sweep_policies(
            plan.statics, stacked, sim.gamma, v_values, kinds, chosen,
            l0=plan.l0, n_devices=plan.n_devices,
            n_gateways=plan.n_gateways)
        return SweepResult(seeds=seeds, v_values=v_values, taus=taus,
                           selected=sel, queues=queues,
                           policies=list(policies))

    policy = sim._resolve_policy(None)
    if not getattr(policy, "traced_decide", False):
        raise ValueError(
            f"Simulation.sweep() needs a traced-decide policy; scenario "
            f"policy {sim.scenario.policy!r} decides on the host — set "
            "Scenario.policy='ddsra_jax'")
    with obs.span("repro.sweep.plan"):
        plan = policy.plan_for(sim.workload, sim.net)
    if not hasattr(plan, "sweep_states"):
        raise ValueError(
            f"policy {sim.scenario.policy!r} has no V-sweep (fixed-resource "
            "baselines ignore V); set Scenario.policy='ddsra_jax' or pass "
            "policies=[...] to sweep them on the policy axis")
    with obs.span("repro.sweep.draw"):
        per_seed = [stack_states(_seed_states(sim, s, T)) for s in seeds]
        stacked = jax.tree.map(lambda *a: np.stack(a), *per_seed)
    taus, sel, queues = plan.sweep_states(stacked, sim.gamma, v_values)
    return SweepResult(seeds=seeds, v_values=v_values, taus=taus,
                       selected=sel, queues=queues)
