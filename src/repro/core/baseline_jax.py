"""Traced fixed-resource baselines: the whole decide trajectory as one scan.

``round_robin`` and ``random`` fix every resource (the Sec. VII-C baseline
contract: partition point ``l = round(0.5 L)``, even gateway-frequency
split, ``p_max`` transmit power) — their per-round work is just the
feasibility check + delay evaluation of
``repro.core.schedulers._fixed_resource_solution`` at the chosen gateways.
That makes the decide trajectory trivially traceable: gateway choice is
data (round-robin's is a closed form of ``t``; random's is pre-drawn
host-side from the policy RNG, preserving the stepwise stream), and the
evaluation reuses the link/cost algebra of ``repro.core.ddsra_jax`` over
the same padded :class:`~repro.core.ddsra_jax._Statics`.

:class:`BaselinePlan` is the baselines' twin of
:class:`~repro.core.ddsra_jax.DDSRAPlan`: built once per (Workload,
Network) pair, its :meth:`~BaselinePlan.decide_scan` runs all rounds as a
single jitted x64 ``lax.scan`` and returns the stacked resolved
:class:`~repro.core.ddsra_jax.RoundDecisionT` the fused simulation loop
consumes — so baseline sweeps fuse end-to-end instead of paying a
host decide loop per round.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import obs
from repro.core.ddsra import Workload
from repro.core.ddsra_jax import (DDSRAPlan, RoundDecisionT, _downlink_time,
                                  _Statics, _uplink_energy, _uplink_time)
from repro.core.lyapunov import update_queues_jax
from repro.core.network import ChannelStateT, Network

# each decide-scan trace bumps ``repro.obs`` counter ``trace.baseline.decide``
# (compile-count tests read it)


def _solve_fixed(s: _Statics, st: ChannelStateT, l0: int, m, j):
    """Feasibility + delay of gateway ``m`` on channel ``j`` at the fixed
    baseline operating point — the traced twin of
    ``repro.core.schedulers._fixed_resource_solution``. Returns (ok, lam)."""
    c = s.cfg
    cumf, cumg = s.cumf, s.cumg
    tot_f, tot_g = cumf[-1], cumg[-1]
    kd, f_dev, valid = s.kd[m], s.f_dev[m], s.valid[m]
    n_loc = s.n_loc[m]
    f_gw = c.f_gw_max / jnp.maximum(n_loc, 1.0)
    e_dev = kd * c.v_dev / c.phi_dev * cumf[l0] * f_dev ** 2
    e_tra = jnp.sum(jnp.where(
        valid, kd * c.v_gw / c.phi_gw * (tot_f - cumf[l0]) * f_gw ** 2,
        0.0))
    h_up, i_up = st.h_up[m, j], st.i_up[m, j]
    e_up = _uplink_energy(c, c.p_max, h_up, i_up, s.gamma)
    e_state = jnp.where(valid, st.e_dev[s.dev_idx[m]], jnp.inf)
    ok = ((cumg[l0] <= c.g_dev_max)
          & (jnp.sum(jnp.where(valid, tot_g - cumg[l0], 0.0))
             <= c.g_gw_max)
          & jnp.all(jnp.where(valid, e_dev <= e_state, True))
          & ((e_tra + e_up) <= st.e_gw[m]))
    top = tot_f - cumf[l0]
    t_dev = cumf[l0] / (c.phi_dev * f_dev)
    t_gw = jnp.where(top > 0,
                     top / jnp.maximum(c.phi_gw * f_gw, 1e-9), 0.0)
    t_train = jnp.max(jnp.where(valid, kd * (t_dev + t_gw), -jnp.inf))
    lam = (t_train + _uplink_time(c, c.p_max, h_up, i_up, s.gamma)
           + _downlink_time(c, st.h_down[m, j], st.i_down[m, j],
                            s.gamma))
    return ok, lam


def _delay_chosen(s: _Statics, st: ChannelStateT, *, l0: int):
    """The delay-driven greedy pick, traced: evaluate every gateway on every
    channel at fixed resources, take each gateway's best-channel delay and
    choose the ``J`` smallest — the jnp twin of
    ``DelayDrivenScheduler.schedule``'s host argsort (jnp's stable argsort
    matches numpy's introselect whenever delays are distinct, which random
    channel draws make almost sure)."""
    m_gw, j_ch = st.h_up.shape

    def best_delay(m):
        _, lam = jax.vmap(lambda j: _solve_fixed(s, st, l0, m, j))(
            jnp.arange(j_ch))
        return jnp.min(lam)

    delays = jax.vmap(best_delay)(jnp.arange(m_gw))       # (M,)
    return jnp.argsort(delays)[:j_ch]


def _baseline_round(s: _Statics, st: ChannelStateT, queues, gamma_rates,
                    chosen, *, l0: int, n_devices: int) -> RoundDecisionT:
    """One fixed-resource baseline round, traced.

    The jnp twin of ``_fixed_resource_solution`` + ``_decision_for`` +
    ``resolve_decision``: evaluate each chosen gateway at the fixed
    ``(l0, f_gw_max/n_loc, p_max)`` operating point, fail infeasible
    selections, scatter the trained gateways' cut into the dense per-device
    vector and run the Eq. (14) queue update.
    """
    m_gw = s.kd.shape[0]
    j_idx = jnp.arange(chosen.shape[0])
    ok_j, lam_j = jax.vmap(
        lambda m, j: _solve_fixed(s, st, l0, m, j))(chosen, j_idx)    # (J,)

    selected = jnp.zeros(m_gw, bool).at[chosen].set(True)
    feas_m = jnp.zeros(m_gw, bool).at[chosen].set(ok_j)
    lam_m = jnp.full(m_gw, jnp.inf).at[chosen].set(lam_j)
    trained = selected & feas_m & jnp.isfinite(lam_m)
    failures = jnp.sum(selected & ~trained)
    gw_delay = jnp.where(trained, lam_m, 0.0)
    delay = jnp.where(trained.any(),
                      jnp.max(jnp.where(trained, lam_m, -jnp.inf)), 0.0)
    # the scheduler-reported tau includes infeasible selections' (finite)
    # delays — _decision_for's max over the assigned lanes
    tau = jnp.max(lam_j)
    vals = jnp.where(s.valid & trained[:, None], jnp.int32(l0), 0)
    l_dev = jnp.zeros((n_devices,), jnp.int32).at[
        s.dev_idx.ravel()].add(vals.ravel())
    new_q = update_queues_jax(queues, selected, gamma_rates)
    return RoundDecisionT(selected=selected, trained=trained, l_dev=l_dev,
                          gw_delay=gw_delay, delay=delay, tau=tau,
                          failures=failures, queues=new_q)


@functools.partial(jax.jit, static_argnames=("l0", "n_devices"))
def _decide_scan(s: _Statics, states: ChannelStateT, queues, gamma_rates,
                 chosen, *, l0: int, n_devices: int) -> RoundDecisionT:
    obs.count("trace.baseline.decide")

    def step(q, xs):
        st, ch = xs
        dec = _baseline_round(s, st, q, gamma_rates, ch,
                              l0=l0, n_devices=n_devices)
        return dec.queues, dec

    _, decisions = lax.scan(step, queues, (states, chosen))
    return decisions


@functools.partial(jax.jit, static_argnames=("l0", "n_devices"))
def _decide_scan_delay(s: _Statics, states: ChannelStateT, queues,
                       gamma_rates, *, l0: int,
                       n_devices: int) -> RoundDecisionT:
    """Delay-driven decide trajectory: the greedy pick is computed in-scan
    from the round's channel draws instead of arriving as data."""
    obs.count("trace.baseline.decide")

    def step(q, st):
        ch = _delay_chosen(s, st, l0=l0)
        dec = _baseline_round(s, st, q, gamma_rates, ch,
                              l0=l0, n_devices=n_devices)
        return dec.queues, dec

    _, decisions = lax.scan(step, queues, states)
    return decisions


@dataclasses.dataclass
class BaselinePlan:
    """Compiled fixed-resource baseline control plane for one
    (Workload, Network) pair — the baselines' :class:`DDSRAPlan` twin.

    Gateway choice is *data* (the ``chosen`` round axis), so one plan
    serves every choice rule: round-robin feeds its closed-form schedule,
    random feeds host-drawn picks from the policy RNG.
    """
    statics: _Statics
    n_devices: int
    n_gateways: int
    n_channels: int
    l0: int                 # the baselines' fixed cut round(0.5 * L)

    @classmethod
    def build(cls, w: Workload, net: Network,
              l_frac: float = 0.5) -> "BaselinePlan":
        d = DDSRAPlan.build(w, net)
        return cls(d.statics, d.n_devices, d.n_gateways, d.n_channels,
                   int(round(l_frac * w.n_layers)))

    def decide_scan(self, states: ChannelStateT, queues, gamma_rates, v, *,
                    chosen=None) -> RoundDecisionT:
        """All rounds' decisions as one compiled x64 program.

        ``chosen`` is the (rounds, J) int array of gateway picks (the only
        thing distinguishing the data-driven baseline policies: round-robin
        feeds its closed form, random its pre-drawn stream). ``chosen=None``
        selects the delay-driven rule, whose greedy pick is a function of
        the round's channel draws and is computed inside the scan. ``v`` is
        accepted for interface parity with :meth:`DDSRAPlan.decide_scan`
        but ignored — fixed-resource baselines have no Lyapunov trade-off.
        """
        del v
        with jax.enable_x64(True):
            states = jax.tree.map(
                lambda a: jnp.asarray(np.asarray(a, np.float64)), states)
            queues = jnp.asarray(np.asarray(queues, np.float64))
            gamma_rates = jnp.asarray(np.asarray(gamma_rates, np.float64))
            if chosen is None:
                return _decide_scan_delay(
                    self.statics, states, queues, gamma_rates,
                    l0=self.l0, n_devices=self.n_devices)
            return _decide_scan(
                self.statics, states, queues, gamma_rates,
                jnp.asarray(np.asarray(chosen, np.int32)),
                l0=self.l0, n_devices=self.n_devices)
