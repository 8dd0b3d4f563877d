"""One-program multi-policy sweeps: policies x seeds x V x rounds.

``repro.core.ddsra_jax._sweep_scan`` fuses a seeds x V DDSRA sweep into one
XLA program, but the paper's headline figures (Figs. 4-6) compare DDSRA
against the fixed-resource baselines — which PR 8 still swept one compiled
program *per policy*. This module folds the policy axis in: every
registered traced-decide rule becomes a numbered branch of one
``lax.switch``, and the whole grid runs as

    vmap(policies) o vmap(seeds) o vmap(V) o lax.scan(rounds)

All three branches read the same padded :class:`~repro.core.ddsra_jax._Statics`
(:meth:`~repro.core.baseline_jax.BaselinePlan.build` already reuses
``DDSRAPlan``'s), so one statics pytree serves the whole grid:

* kind 0 — ``ddsra_jax``: the full Algorithm 1 round solve
  (:func:`repro.core.ddsra_jax._round`);
* kind 1 — fixed-chosen baselines (``round_robin``, ``random``): gateway
  picks are *data* fed down the scan's round axis (round-robin's closed
  form, random's pre-drawn per-seed policy-RNG stream), evaluated by
  :func:`repro.core.baseline_jax._baseline_round`;
* kind 2 — ``delay_driven``: the greedy pick is a function of the round's
  channel draws, computed in-scan by
  :func:`repro.core.baseline_jax._delay_chosen`.

The policy axis is unrolled at *trace* time (``kinds`` is a static tuple)
rather than dispatched through a runtime one-hot ``lax.switch``: under
``vmap`` a switch lowers to computing every branch for every lane and
masking — P x the control-plane work — while the unrolled form stays ONE
compiled program (one ``jit`` entry, the per-policy grids stacked inside)
in which each lane computes only its own branch. One compile per distinct
policy tuple; re-running with different seeds/V/queues never retraces.
Baseline lanes ignore V (no Lyapunov trade-off), so their rows repeat
across the V axis — the flat curves of Figs. 4-6.

Row (p, s, v) is pinned bit-identical (queues, selection) to a stepwise
``reset(seeds[s])`` run of policy ``policies[p]`` at ``v_values[v]``
(``tests/test_fused_sim.py``), and the cross-process digest test freezes
the whole grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import obs
from repro.core.baseline_jax import _baseline_round, _delay_chosen
from repro.core.ddsra_jax import (RoundContextT, _round, _Statics,
                                  resolve_decision_arrays)
from repro.core.network import ChannelStateT

# policy name -> switch branch index. Only traced-decide policies can ride
# the fused sweep; host-loop rules (``ddsra`` oracle, ``loss_driven``) are
# refused by Simulation.sweep with a pointer to Simulation.rounds().
POLICY_KINDS = {"ddsra_jax": 0, "round_robin": 1, "random": 1,
                "delay_driven": 2}

# each sweep trace bumps ``repro.obs`` counter ``trace.policy_sweep.sweep``
# (compile-count tests read it): one compile per (topology, P, S, V, T)
# shape, never per policy.


@functools.partial(jax.jit, static_argnames=("kinds", "l0", "n_devices"))
def _policy_sweep_scan(s: _Statics, states: ChannelStateT, queues0,
                       gamma_rates, chosen, v_values, *,
                       kinds: tuple, l0: int, n_devices: int):
    """The fused grid. ``states`` leaves carry (S, T, ...), ``kinds`` is a
    static tuple of branch indices (one per policy lane, unrolled at trace
    time), ``chosen`` (P, S, T, J) gateway picks (read only by kind-1
    lanes; zeros elsewhere). Returns (taus, selected, queues) with leading
    (P, S, V, T) axes."""
    obs.count("trace.policy_sweep.sweep")

    def policy_round(kind, q, st, ch, v):
        # every branch emits the *realized* round delay (max over trained
        # gateways, 0 when nobody trains) — the stepwise RoundRecord.delay
        # the parity test compares against. For ddsra the cap-sweep only
        # assigns feasible lanes so realized == scheduler tau; the
        # baselines can select infeasible gateways, where the two differ.
        if kind == 0:
            out = _round(s, st, RoundContextT(q, gamma_rates, v))
            dec = resolve_decision_arrays(s, out, n_devices)
            return dec.delay, out.selected, out.queues
        if kind == 2:
            ch = _delay_chosen(s, st, l0=l0)
        dec = _baseline_round(s, st, q, gamma_rates, ch,
                              l0=l0, n_devices=n_devices)
        return dec.delay, dec.selected, dec.queues

    def run_lane(kind, states_1, chosen_1, v):
        def step(q, xs):
            st, ch = xs
            tau, sel, new_q = policy_round(kind, q, st, ch, v)
            return new_q, (tau, sel, new_q)

        _, ys = lax.scan(step, queues0, (states_1, chosen_1))
        return ys

    def grid(kind, chosen_p):
        def over_v(states_1, chosen_1):
            return jax.vmap(lambda v: run_lane(kind, states_1, chosen_1,
                                               v))(v_values)
        return jax.vmap(over_v)(states, chosen_p)

    per_policy = [grid(kind, chosen[pi]) for pi, kind in enumerate(kinds)]
    return jax.tree.map(lambda *a: jnp.stack(a), *per_policy)


def sweep_policies(statics: _Statics, states: ChannelStateT, gamma_rates,
                   v_values, kinds, chosen, *, l0: int, n_devices: int,
                   n_gateways: int, queues=None):
    """Host entry: cast to the x64 control plane, run the fused grid and
    concretize. ``states`` leaves are (S, T, ...) host stacks; returns
    numpy (taus, selected, queues) shaped (P, S, V, T[, M])."""
    with jax.enable_x64(True):
        with obs.span("repro.sweep.dispatch"):
            states = jax.tree.map(
                lambda a: jnp.asarray(np.asarray(a, np.float64)), states)
            q0 = np.zeros(n_gateways) if queues is None else queues
            taus, sel, qs = _policy_sweep_scan(
                statics, states,
                jnp.asarray(np.asarray(q0, np.float64)),
                jnp.asarray(np.asarray(gamma_rates, np.float64)),
                jnp.asarray(np.asarray(chosen, np.int32)),
                jnp.asarray(np.asarray(v_values, np.float64)),
                kinds=tuple(int(k) for k in kinds),
                l0=l0, n_devices=n_devices)
        with obs.span("repro.sweep.wait"):
            taus = np.asarray(taus)
        with obs.span("repro.sweep.fetch"):
            return taus, np.asarray(sel), np.asarray(qs)
