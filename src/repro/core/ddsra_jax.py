"""Jitted DDSRA control plane: the numpy Algorithm 1, vectorized in JAX.

``repro.core.ddsra`` is the host-side oracle: Python loops over every
(gateway m, channel j) pair, 40-trip scalar bisections for the partition /
frequency / power sub-problems (21)-(24), and a Python Kuhn-Munkres per
lambda cap for the channel assignment (26)-(29).  This module is the same
algorithm as data-parallel XLA:

* the per-(m, j) block-coordinate descent is ``vmap``-ed over all M x J
  pairs at once (the paper marks these solves "do in parallel");
* every bisection becomes a fixed-trip ``lax.scan`` (identical lo/hi/mid
  trajectory, infeasibility carried as a sticky mask instead of an early
  ``return None``), so the whole solve is branch-free;
* the lambda-cap sweep maps the jittable Kuhn-Munkres
  (:func:`repro.core.hungarian.hungarian_min_jax`) over all M*J caps and
  replicates the oracle's first-wins / 1e-12-improvement selection with a
  small ``lax.scan``;
* the channel/energy draw and the Lyapunov queue update (14) are also
  expressed in JAX, so a whole scheduling step is one jitted function of
  ``(key, queues)`` — which makes batched sweeps (``vmap`` over V values or
  seeds, ``lax.scan`` over rounds) single XLA programs
  (:meth:`DDSRAPlan.simulate_v_sweep`, used by
  ``benchmarks/theorem2_tradeoff.py``).

Precision: the numpy oracle is implicitly float64, and the bisections
resolve constraint boundaries far below float32's ~1e-7 relative grid, so
the jitted control plane always runs in **x64** (entry points trace and
execute under ``jax.enable_x64(True)`` regardless of the global flag; the
data plane stays f32). Parity with the oracle — identical assignments /
selected sets, Lambda and tau within 1e-6 — is pinned in
``tests/test_ddsra_jax.py``.

Ragged shop floors are padded: per-gateway device vectors are (M, n_max)
with a validity mask; padded lanes carry ``d_tilde = 0`` and are masked
out of every reduction, so they contribute exact zeros and never flip a
feasibility test.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import obs
from repro.core.ddsra import (GatewaySolution, RoundDecision, Workload, _PSI,
                              _cum)
from repro.core.hungarian import assign_channels_jax
from repro.core.lyapunov import update_queues_jax
from repro.core.network import (ChannelState, ChannelStateT, Network,
                                draw_state_jax)

_BCD_ITERS = 4        # block-coordinate descent sweeps (oracle: bcd_iters)
_PART_ITERS = 40      # bisection trips for (21), (22), (23)/(24)
_FREQ_ITERS = 40
_POW_ITERS = 60

# The traced bodies bump ``repro.obs`` counters (Python side effects run only
# at trace time): ``trace.ddsra.round`` per stepwise round trace,
# ``trace.ddsra.decide`` per fused decide-scan trace, ``trace.ddsra.sweep``
# per seeds x V sweep trace. Tests assert exact compile counts against these
# (tests/conftest.py ``compile_count``).


class _Cfg(NamedTuple):
    """NetworkConfig scalars as traced leaves (no recompile across nets)."""
    phi_dev: jnp.ndarray
    phi_gw: jnp.ndarray
    v_dev: jnp.ndarray
    v_gw: jnp.ndarray
    f_gw_max: jnp.ndarray
    f_gw_min: jnp.ndarray
    g_dev_max: jnp.ndarray
    g_gw_max: jnp.ndarray
    p_max: jnp.ndarray
    p_bs: jnp.ndarray
    b_up: jnp.ndarray
    b_down: jnp.ndarray
    n0: jnp.ndarray
    e_dev_max: jnp.ndarray
    e_gw_max: jnp.ndarray
    i_up_var: jnp.ndarray
    i_down_var: jnp.ndarray


class _Statics(NamedTuple):
    """Per-(workload, network) arrays: everything the round solve reads."""
    cfg: _Cfg
    cumf: jnp.ndarray       # (L+1,) cumulative FLOPs prefix
    cumg: jnp.ndarray       # (L+1,) cumulative memory prefix
    gamma: jnp.ndarray      # model size, bytes
    kd: jnp.ndarray         # (M, n_max) K * d_tilde, 0 on padded lanes
    f_dev: jnp.ndarray      # (M, n_max) device frequency, 1.0 on padding
    valid: jnp.ndarray      # (M, n_max) bool
    n_loc: jnp.ndarray      # (M,) devices per gateway (float)
    dev_idx: jnp.ndarray    # (M, n_max) int32 device index, 0 on padding
    path: jnp.ndarray       # (M,) path-loss factor for the JAX channel draw


# One round's ChannelState as a pytree — shared with repro.core.network
# (the fused-simulation contract; was a private _St twin here).
_St = ChannelStateT


class RoundContextT(NamedTuple):
    """Traced twin of ``repro.core.schedulers.RoundContext``: the per-round
    scheduling inputs as a pytree, so a whole trajectory of contexts is one
    stacked pytree a ``lax.scan`` can thread. Only the tensors the traced
    DDSRA round actually reads are carried — the host RoundContext's object
    references (net, workload) live in :class:`_Statics` instead."""
    queues: jnp.ndarray        # (M,) virtual-queue backlog Q_m(t)
    gamma_rates: jnp.ndarray   # (M,) participation-rate targets
    v: jnp.ndarray             # scalar Lyapunov trade-off weight


class DecisionArrays(NamedTuple):
    """Raw per-round DDSRA solver outputs as a typed pytree (was an untyped
    dict): everything Algorithm 1 decides, padded-dense over (M, J[, n_max])
    so rounds stack/scan without shape games. ``repro.fl.fused_sim`` threads
    these straight into the fused training round without leaving the device;
    :meth:`DDSRAPlan.round` repackages them as the oracle's
    :class:`RoundDecision` for the stepwise host path."""
    feasible: jnp.ndarray      # (M, J) bool
    lam: jnp.ndarray           # (M, J) round delay Lambda_{m,j} (inf = infeasible)
    l: jnp.ndarray             # (M, J, n_max) partition points
    f_gw: jnp.ndarray          # (M, J, n_max) gateway frequency split
    p_tx: jnp.ndarray          # (M, J) transmit power
    e_dev: jnp.ndarray         # (M, J, n_max) device energy used
    e_gw: jnp.ndarray          # (M, J) gateway energy used
    eye: jnp.ndarray           # (M, J) channel assignment indicator
    selected: jnp.ndarray      # (M,) bool participation
    tau: jnp.ndarray           # scalar round delay
    queues: jnp.ndarray        # (M,) post-update queues (Eq. 14)


class RoundDecisionT(NamedTuple):
    """Pytree-typed :class:`repro.core.ddsra.RoundDecision`: the *resolved*
    schedule in the exact form the fused training round consumes — per-device
    partition points scattered out of the padded (M, J, n_max) lanes, the
    trained mask with infeasible selections already failed out, and the
    realized delay. Produced traced by :func:`resolve_decision_arrays`
    (inside the fused scan) and host-side by
    ``repro.fl.sim.resolve_decision`` (the stepwise loop); the parity
    matrix pins the two bit-identical."""
    selected: jnp.ndarray      # (M,) bool scheduled participation
    trained: jnp.ndarray       # (M,) bool actually-training gateways
    l_dev: jnp.ndarray         # (N,) per-device partition points
    gw_delay: jnp.ndarray      # (M,) per-gateway delay (0 where not trained)
    delay: jnp.ndarray         # scalar realized round delay (max over trained)
    tau: jnp.ndarray           # scalar scheduler-reported round delay
    failures: jnp.ndarray      # scalar count of infeasible selections
    queues: jnp.ndarray        # (M,) post-update queues


# ---------------------------------------------------------------------------
# masked reductions over the padded device lane
# ---------------------------------------------------------------------------


def _msum(x, valid):
    return jnp.sum(jnp.where(valid, x, 0.0))


def _mmax(x, valid):
    return jnp.max(jnp.where(valid, x, -jnp.inf))


def _mmin(x, valid):
    return jnp.min(jnp.where(valid, x, jnp.inf))


def _mall(cond, valid):
    return jnp.all(jnp.where(valid, cond, True))


# ---------------------------------------------------------------------------
# link model (network.py's rate/time/energy, traced)
# ---------------------------------------------------------------------------


def _uplink_time(c: _Cfg, p, h, i_up, gamma):
    sinr = p * h / (c.b_up * c.n0 + i_up)
    r = c.b_up * jnp.log2(1.0 + sinr)
    return jnp.where(r > 0, gamma * 8.0 / r, jnp.inf)


def _uplink_energy(c: _Cfg, p, h, i_up, gamma):
    return p * _uplink_time(c, p, h, i_up, gamma)


def _downlink_time(c: _Cfg, h, i_down, gamma):
    sinr = c.p_bs * h / (c.b_down * c.n0 + i_down)
    r = c.b_down * jnp.log2(1.0 + sinr)
    return jnp.where(r > 0, gamma * 8.0 / r, jnp.inf)


# ---------------------------------------------------------------------------
# inner solvers for one (gateway, channel): fixed-trip lax.scan bisections
# ---------------------------------------------------------------------------


def _bisect(feasible, lo, hi, best0, iters: int):
    """The oracle's bisection: keep the feasible side, carry the last
    feasible payload. ``best0`` must be ``feasible(hi)``'s payload."""

    def trip(carry, _):
        lo, hi, best = carry
        mid = 0.5 * (lo + hi)
        ok, sol = feasible(mid)
        lo = jnp.where(ok, lo, mid)
        hi = jnp.where(ok, mid, hi)
        best = jax.tree.map(lambda new, old: jnp.where(ok, new, old),
                            sol, best)
        return (lo, hi, best), None

    (_, _, best), _ = lax.scan(trip, (lo, hi, best0), None, length=iters)
    return best


def _solve_partition(c: _Cfg, cumf, cumg, kd, f_dev, valid, e_dev, f_gw,
                     e_gw_budget):
    """Sub-problem (21): bisection on eta; returns (feasible, l per lane)."""
    big_l = cumf.shape[0] - 1
    tot_f, tot_g = cumf[-1], cumg[-1]

    # per-device static upper bounds from C7' (memory) and C10' (energy)
    mem_ok = cumg <= c.g_dev_max                               # (L+1,)
    e_grid = (kd * c.v_dev / c.phi_dev * f_dev ** 2)[:, None] * cumf[None, :]
    ok_static = mem_ok[None, :] & (e_grid <= e_dev[:, None])
    static_ok = _mall(ok_static.any(axis=1), valid)
    hi_static = big_l - jnp.argmax(ok_static[:, ::-1], axis=1)

    # per-device time at every cut, hoisted out of the bisection
    t_grid = kd[:, None] * (
        cumf[None, :] / (c.phi_dev * f_dev)[:, None]
        + (tot_f - cumf[None, :])
        / jnp.maximum(c.phi_gw * f_gw, 1e-9)[:, None])
    ls_ok_static = jnp.arange(big_l + 1)[None, :] <= hi_static[:, None]
    gw_e_coef = kd * c.v_gw / c.phi_gw * f_gw ** 2

    def feasible(eta):
        """Largest l per device with time <= eta, then joint C8'/C9'."""
        ok = (t_grid <= eta) & ls_ok_static
        l_pick = big_l - jnp.argmax(ok[:, ::-1], axis=1)
        per_dev_ok = _mall(ok.any(axis=1), valid)
        mem_ok_gw = _msum(tot_g - cumg[l_pick], valid) <= c.g_gw_max
        e_ok_gw = _msum(gw_e_coef * (tot_f - cumf[l_pick]),
                        valid) <= e_gw_budget
        return per_dev_ok & mem_ok_gw & e_ok_gw, l_pick

    lo = jnp.zeros_like(tot_f)
    hi = _mmax(kd, valid) * tot_f / jnp.minimum(
        c.phi_dev * _mmin(f_dev, valid),
        c.phi_gw * jnp.maximum(_mmin(f_gw, valid), 1e-9))
    ok_hi, best0 = feasible(hi)
    best = _bisect(feasible, lo, hi, best0, _PART_ITERS)
    return static_ok & ok_hi, best


def _solve_frequency(c: _Cfg, cumf, kd, f_dev, valid, n_loc, l, e_gw_budget):
    """Sub-problem (22): bisection on theta; returns (feasible, f per lane)."""
    tot = cumf[-1]
    dev_t = cumf[l] / (c.phi_dev * f_dev)        # per-sample device time
    gw_work = (tot - cumf[l]) / c.phi_gw         # cycles on gateway
    all_on_device = _mall(gw_work <= 0, valid)
    f_floor = c.f_gw_min / jnp.maximum(n_loc, 1.0)

    def f_of(theta):
        denom = theta / kd - dev_t               # padded: kd=0 -> +inf
        denom_ok = _mall(denom > 0, valid)
        f = jnp.where(valid, jnp.maximum(gw_work / denom, 0.0), 0.0)
        sum_ok = jnp.sum(f) <= c.f_gw_max
        e = _msum(kd * c.v_gw * gw_work * f ** 2, valid)
        return denom_ok & sum_ok & (e <= e_gw_budget), f

    lo = _mmax(kd * (dev_t + gw_work / c.f_gw_max), valid)
    hi = _mmax(kd * (dev_t + gw_work / jnp.maximum(f_floor, 1e3)), valid)
    hi = jnp.maximum(hi, lo * 4 + 1.0)
    ok_hi, best0 = f_of(hi)
    best = _bisect(f_of, lo, hi, best0, _FREQ_ITERS)

    feas = jnp.where(all_on_device, True, ok_hi)
    f = jnp.where(all_on_device, jnp.where(valid, f_floor, 0.0), best)
    return feas, f


def _solve_power(c: _Cfg, h_up, i_up, gamma, e_budget):
    """(23)/(24): largest transmit power whose upload energy fits.

    Opposite bisection direction from (21)/(22): a feasible mid *raises*
    ``lo`` (we want the largest feasible power), and ``lo`` is returned."""

    def fits(p):
        return _uplink_energy(c, p, h_up, i_up, gamma) <= e_budget

    def trip(carry, _):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        ok = fits(mid)
        return (jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)), None

    (lo, _), _ = lax.scan(trip, (jnp.zeros_like(e_budget), c.p_max),
                          None, length=_POW_ITERS)
    p = jnp.where(fits(c.p_max), c.p_max, lo)
    return jnp.where(e_budget <= 0, 0.0, p)


def _solve_gateway(s: _Statics, kd, f_dev, valid, n_loc, e_dev, e_gw_m,
                   h_up, h_down, i_up, i_down):
    """Full BCD for one (m, j) — the traced twin of ``solve_gateway``.

    All carries are frozen the moment a sub-solve fails (sticky ``feas``
    mask), mirroring the oracle's early ``return infeasible``.
    """
    c = s.cfg
    cumf, cumg = s.cumf, s.cumg
    tot = cumf[-1]
    n_max = kd.shape[0]

    feas = n_loc > 0
    l = jnp.zeros(n_max, jnp.int32)
    f_gw = jnp.full(n_max, c.f_gw_max / jnp.maximum(n_loc, 1.0))
    p_tx = c.p_max * jnp.ones(())
    e_tra_gw = jnp.zeros(())

    for _ in range(_BCD_ITERS):
        e_up = _uplink_energy(c, p_tx, h_up, i_up, s.gamma)
        e_budget = e_gw_m - e_up
        ok_l, l_new = _solve_partition(c, cumf, cumg, kd, f_dev, valid,
                                       e_dev, f_gw, e_budget)
        ok_l = feas & ok_l
        l = jnp.where(ok_l, l_new, l)
        ok_f, f_new = _solve_frequency(c, cumf, kd, f_dev, valid, n_loc,
                                       l_new, e_budget)
        ok_f = ok_l & ok_f
        f_cand = jnp.maximum(f_new, 1e3)
        f_gw = jnp.where(ok_f, f_cand, f_gw)
        e_tra_new = _msum(kd * c.v_gw / c.phi_gw * (tot - cumf[l_new])
                          * f_cand ** 2, valid)
        e_tra_gw = jnp.where(ok_f, e_tra_new, e_tra_gw)
        p_new = _solve_power(c, h_up, i_up, s.gamma, e_gw_m - e_tra_new)
        ok_p = ok_f & (p_new > 0)
        p_tx = jnp.where(ok_p, p_new, p_tx)
        feas = ok_p

    # Lambda_{m,j} (18) and the emitted resources
    t_dev = cumf[l] / (c.phi_dev * f_dev)
    top = tot - cumf[l]
    t_gw = jnp.where(top > 0,
                     top / jnp.maximum(c.phi_gw * f_gw, 1e-9), 0.0)
    t_train = _mmax(kd * (t_dev + t_gw), valid)
    lam = (t_train + _uplink_time(c, p_tx, h_up, i_up, s.gamma)
           + _downlink_time(c, h_down, i_down, s.gamma))
    lam = jnp.where(feas, lam, jnp.inf)
    e_dev_used = kd * c.v_dev / c.phi_dev * cumf[l] * f_dev ** 2
    e_gw_used = e_tra_gw + _uplink_energy(c, p_tx, h_up, i_up, s.gamma)
    return feas, lam, l, f_gw, p_tx, e_dev_used, e_gw_used


# ---------------------------------------------------------------------------
# channel assignment (26)-(29): vmapped Hungarian over the lambda-cap sweep
# ---------------------------------------------------------------------------


def _assignment(lam, queues, v):
    """The oracle's cap sweep, batched: sort all M*J delays descending
    (a superset of ``np.unique(...)[::-1]`` — duplicates re-evaluate to the
    identical assignment and lose the strict-improvement test), solve the
    Theta assignment at every cap with the vmapped jittable Hungarian, and
    replay the first-wins / 1e-12 objective selection with a scan."""
    m_gw, j_ch = lam.shape
    finite = jnp.isfinite(lam)
    caps = jnp.sort(jnp.where(finite, lam, -jnp.inf).ravel())[::-1]

    def eval_cap(cap):
        allowed = finite & (lam <= cap + 1e-12)
        theta = jnp.where(allowed, -queues[:, None], _PSI)
        # a feasible assignment needs >=1 allowed gateway per channel
        ch_ok = ~jnp.any(jnp.all(theta >= _PSI, axis=0))
        eye = assign_channels_jax(theta)
        banned = jnp.any(jnp.where(eye > 0, theta, 0.0) >= _PSI)
        tau = jnp.max(jnp.where(eye > 0, lam, -jnp.inf))
        obj = v * tau - jnp.sum(queues * eye.sum(axis=1))
        return jnp.isfinite(cap) & ch_ok & ~banned, obj, eye

    cap_ok, objs, eyes = jax.vmap(eval_cap)(caps)

    def pick(carry, x):
        best_obj, best_idx, found = carry
        ok, obj, idx = x
        better = ok & (~found | (obj < best_obj - 1e-12))
        return (jnp.where(better, obj, best_obj),
                jnp.where(better, idx, best_idx),
                found | ok), None

    (_, best_idx, found), _ = lax.scan(
        pick, (jnp.inf, jnp.int32(0), jnp.asarray(False)),
        (cap_ok, objs, jnp.arange(caps.shape[0], dtype=jnp.int32)))
    eye = jnp.where(found, eyes[best_idx], jnp.zeros((m_gw, j_ch)))
    selected = eye.sum(axis=1) > 0
    tau = jnp.where(selected.any(),
                    jnp.max(jnp.where(eye > 0, lam, -jnp.inf)), 0.0)
    return eye, selected, tau


# ---------------------------------------------------------------------------
# the fused round + the jitted entry points
# ---------------------------------------------------------------------------


def _round(s: _Statics, st: ChannelStateT, ctx: RoundContextT
           ) -> DecisionArrays:
    """One whole DDSRA round as a single traced computation."""
    obs.count("trace.ddsra.round")
    e_dev_pad = jnp.where(s.valid, st.e_dev[s.dev_idx], jnp.inf)

    solve = _solve_gateway
    # inner vmap over channels j (gateway arrays broadcast), outer over m
    solve = jax.vmap(solve, in_axes=(None, None, None, None, None, None,
                                     None, 0, 0, 0, 0))
    solve = jax.vmap(solve, in_axes=(None, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0))
    feas, lam, l, f_gw, p_tx, e_dev_used, e_gw_used = solve(
        s, s.kd, s.f_dev, s.valid, s.n_loc, e_dev_pad, st.e_gw,
        st.h_up, st.h_down, st.i_up, st.i_down)

    eye, selected, tau = _assignment(lam, ctx.queues, ctx.v)
    # Eq. (14)
    new_q = update_queues_jax(ctx.queues, selected, ctx.gamma_rates)
    return DecisionArrays(feasible=feas, lam=lam, l=l, f_gw=f_gw, p_tx=p_tx,
                          e_dev=e_dev_used, e_gw=e_gw_used, eye=eye,
                          selected=selected, tau=tau, queues=new_q)


_round_jit = jax.jit(_round)


def resolve_decision_arrays(s: _Statics, out: DecisionArrays,
                            n_devices: int) -> RoundDecisionT:
    """Resolve raw solver outputs into the engine-facing
    :class:`RoundDecisionT` — the traced twin of
    ``repro.fl.sim.resolve_decision`` (same semantics, array form):

    * each selected gateway's assigned channel is the argmax of its ``eye``
      row (exactly one 1 when selected);
    * a selection whose solve is infeasible (or non-finite delay) *fails*
      instead of training — counted in ``failures``;
    * the per-lane partition points of trained gateways scatter into the
      dense (N,) ``l_dev`` vector (padded lanes carry ``dev_idx=0`` but
      scatter exact zeros, so they never corrupt device 0);
    * the realized round delay is the max over trained gateways (the FedAvg
      barrier), 0 when nobody trains.
    """
    m_idx = jnp.arange(out.eye.shape[0])
    j_star = jnp.argmax(out.eye, axis=1)                    # (M,)
    lam_sel = out.lam[m_idx, j_star]
    feas_sel = out.feasible[m_idx, j_star]
    trained = out.selected & feas_sel & jnp.isfinite(lam_sel)
    failures = jnp.sum(out.selected & ~trained)
    l_sel = out.l[m_idx, j_star]                            # (M, n_max)
    vals = jnp.where(s.valid & trained[:, None], l_sel, 0)
    l_dev = jnp.zeros((n_devices,), out.l.dtype).at[
        s.dev_idx.ravel()].add(vals.ravel())
    gw_delay = jnp.where(trained, lam_sel, 0.0)
    delay = jnp.where(trained.any(),
                      jnp.max(jnp.where(trained, lam_sel, -jnp.inf)), 0.0)
    return RoundDecisionT(selected=out.selected, trained=trained,
                          l_dev=l_dev, gw_delay=gw_delay, delay=delay,
                          tau=out.tau, failures=failures, queues=out.queues)


@functools.partial(jax.jit, static_argnames=("n_devices",))
def _decide_scan(s: _Statics, states: ChannelStateT, ctx0: RoundContextT,
                 *, n_devices: int):
    """Whole decide trajectory as one program: ``lax.scan`` the traced
    DDSRA round over stacked channel states, threading only the queue
    vector. Returns the stacked :class:`RoundDecisionT` (leading round
    axis) plus the stacked raw :class:`DecisionArrays` queues trajectory's
    final value via the decisions themselves."""
    obs.count("trace.ddsra.decide")

    def step(queues, st):
        out = _round(s, st, ctx0._replace(queues=queues))
        return out.queues, resolve_decision_arrays(s, out, n_devices)

    _, decisions = lax.scan(step, ctx0.queues, states)
    return decisions


@jax.jit
def _sweep_scan(s: _Statics, states: ChannelStateT, ctx0: RoundContextT,
                v_values):
    """seeds x V sweep as one program: ``vmap`` over the seed axis of the
    stacked states (leaves (S, T, ...)), ``vmap`` over V (all lanes share a
    seed's channel draws — the fair-sweep contract), ``lax.scan`` over
    rounds. Returns (taus, selected, queues) with leading (S, V, T) axes."""
    obs.count("trace.ddsra.sweep")

    def run_v(states_1seed, v):
        def step(queues, st):
            out = _round(s, st, ctx0._replace(queues=queues, v=v))
            return out.queues, (out.tau, out.selected, out.queues)
        _, ys = lax.scan(step, ctx0.queues, states_1seed)
        return ys

    per_seed = jax.vmap(lambda st1: jax.vmap(
        lambda v: run_v(st1, v))(v_values))
    return per_seed(states)


@dataclasses.dataclass
class DDSRAPlan:
    """Compiled control plane for one (Workload, Network) pair.

    Build once per simulation (``DDSRAPlan.build``); ``round(st, ...)``
    then runs the whole Algorithm 1 step as one jitted x64 program and
    repackages the outputs as the oracle's :class:`RoundDecision`.
    """
    statics: _Statics
    n_devices: int
    n_gateways: int
    n_channels: int
    n_max: int
    n_loc_host: np.ndarray      # (M,) int — for slicing padded lanes

    @classmethod
    def build(cls, w: Workload, net: Network) -> "DDSRAPlan":
        cfg = net.cfg
        m_gw, n_dev = cfg.n_gateways, cfg.n_devices
        counts = np.bincount(net.assign, minlength=m_gw)
        n_max = max(int(counts.max()), 1)
        kd = np.zeros((m_gw, n_max))
        f_dev = np.ones((m_gw, n_max))
        valid = np.zeros((m_gw, n_max), bool)
        dev_idx = np.zeros((m_gw, n_max), np.int32)
        for m in range(m_gw):
            devs = net.devices_of(m)
            kd[m, :len(devs)] = w.k_iters * w.d_tilde[devs]
            f_dev[m, :len(devs)] = net.f_dev[devs]
            valid[m, :len(devs)] = True
            dev_idx[m, :len(devs)] = devs
        with jax.enable_x64(True):
            c = _Cfg(*[jnp.asarray(float(x)) for x in (
                cfg.phi_dev, cfg.phi_gw, cfg.v_dev, cfg.v_gw, cfg.f_gw_max,
                cfg.f_gw_min, cfg.g_dev_max, cfg.g_gw_max, cfg.p_max,
                cfg.p_bs, cfg.bandwidth_up, cfg.bandwidth_down, net.n0,
                cfg.e_dev_max, cfg.e_gw_max, cfg.interference_up_var,
                cfg.interference_down_var)])
            statics = _Statics(
                cfg=c,
                cumf=jnp.asarray(_cum(w.flops)),
                cumg=jnp.asarray(_cum(w.mem)),
                gamma=jnp.asarray(float(w.gamma)),
                kd=jnp.asarray(kd), f_dev=jnp.asarray(f_dev),
                valid=jnp.asarray(valid),
                n_loc=jnp.asarray(counts.astype(float)),
                dev_idx=jnp.asarray(dev_idx),
                path=jnp.asarray(net.h0 * (cfg.d0 / net.dist) ** cfg.nu))
        return cls(statics, n_dev, m_gw, cfg.n_channels, n_max,
                   counts.astype(int))

    # -- one oracle-parity round ----------------------------------------

    def _ctx(self, queues, gamma_rates, v) -> RoundContextT:
        """Host values -> the x64 traced context pytree."""
        return RoundContextT(
            queues=jnp.asarray(np.asarray(queues, np.float64)),
            gamma_rates=jnp.asarray(np.asarray(gamma_rates, np.float64)),
            v=jnp.asarray(float(v)))

    def round_arrays(self, st: ChannelState, queues, gamma_rates, v
                     ) -> DecisionArrays:
        """Run the jitted round on a host-drawn ChannelState; returns the
        raw :class:`DecisionArrays` pytree of device arrays (x64)."""
        with jax.enable_x64(True):
            return _round_jit(self.statics, ChannelStateT.of(st),
                              self._ctx(queues, gamma_rates, v))

    def round(self, st: ChannelState, queues, gamma_rates, v
              ) -> RoundDecision:
        """Oracle-compatible round: jitted solve + host repackaging."""
        out = self.round_arrays(st, queues, gamma_rates, v)
        eye = np.asarray(out.eye)
        lam = np.asarray(out.lam)
        feas = np.asarray(out.feasible)
        l = np.asarray(out.l)
        f_gw = np.asarray(out.f_gw)
        p_tx = np.asarray(out.p_tx)
        e_dev = np.asarray(out.e_dev)
        e_gw = np.asarray(out.e_gw)
        sols = {}
        for m, j in zip(*np.nonzero(eye > 0)):
            n = int(self.n_loc_host[m])
            sols[(int(m), int(j))] = GatewaySolution(
                bool(feas[m, j]), float(lam[m, j]),
                l[m, j, :n].astype(int), f_gw[m, j, :n],
                float(p_tx[m, j]), e_dev[m, j, :n], float(e_gw[m, j]))
        selected = eye.sum(axis=1) > 0
        return RoundDecision(eye, selected, lam, sols,
                             float(out.tau), np.asarray(out.queues))

    # -- fused decide trajectories (repro.fl.fused_sim) ------------------

    def decide_scan(self, states: ChannelStateT, queues, gamma_rates, v
                    ) -> RoundDecisionT:
        """Run the whole decide trajectory as one compiled program.

        ``states`` is a stacked :class:`ChannelStateT` (leading round axis,
        host-drawn so the numpy channel stream is preserved); returns the
        stacked resolved :class:`RoundDecisionT` with every leaf carrying a
        leading ``(rounds,)`` axis. One compile per (topology, rounds)
        shape; re-running with different values never retraces.
        """
        with jax.enable_x64(True):
            states = jax.tree.map(
                lambda a: jnp.asarray(np.asarray(a, np.float64)), states)
            return _decide_scan(self.statics, states,
                                self._ctx(queues, gamma_rates, v),
                                n_devices=self.n_devices)

    def sweep_states(self, states: ChannelStateT, gamma_rates, v_values,
                     queues=None):
        """seeds x V sweep over host-drawn channel trajectories as one
        compiled program.

        ``states`` leaves carry leading (seeds, rounds) axes (stack
        ``repro.core.network.stack_states`` per seed, then ``np.stack``
        over seeds). All V lanes of a seed share its channel draws — the
        PR 2 fair-sweep contract — so the trade-off curves isolate V.
        Returns numpy (taus, selected, queues) shaped
        (seeds, len(v_values), rounds[, M]).
        """
        with jax.enable_x64(True):
            with obs.span("repro.sweep.dispatch"):
                states = jax.tree.map(
                    lambda a: jnp.asarray(np.asarray(a, np.float64)), states)
                q0 = np.zeros(self.n_gateways) if queues is None else queues
                taus, sel, qs = _sweep_scan(
                    self.statics, states, self._ctx(q0, gamma_rates, 0.0),
                    jnp.asarray(np.asarray(v_values, np.float64)))
            with obs.span("repro.sweep.wait"):
                taus = np.asarray(taus)
            with obs.span("repro.sweep.fetch"):
                return taus, np.asarray(sel), np.asarray(qs)

    # -- fully-fused sweeps (device-resident rounds) ---------------------

    def simulate_v_sweep(self, key, gamma_rates, v_values, rounds: int):
        """vmap-over-V DDSRA runs, channel draws on device: one XLA program
        computes (taus, selected) of shape (len(v_values), rounds[, M]).

        All V lanes share the same per-round channel keys (the fair-sweep
        contract), so the trade-off curve isolates V."""
        with jax.enable_x64(True):
            s = self.statics
            n_dev, j_ch = self.n_devices, self.n_channels
            gamma_rates = jnp.asarray(np.asarray(gamma_rates, np.float64))
            v_values = jnp.asarray(np.asarray(v_values, np.float64))
            keys = jax.random.split(jax.random.PRNGKey(0) if key is None
                                    else key, rounds)

            def one_round(q, key, v):
                c = s.cfg
                st = ChannelStateT(*draw_state_jax(
                    key, s.path, j_ch, n_dev,
                    e_dev_max=c.e_dev_max, e_gw_max=c.e_gw_max,
                    i_up_var=c.i_up_var, i_down_var=c.i_down_var))
                out = _round(s, st, RoundContextT(q, gamma_rates, v))
                return out.queues, (out.tau, out.selected)

            def run_v(v):
                def step(q, key):
                    return one_round(q, key, v)
                _, (taus, sel) = lax.scan(
                    step, jnp.zeros(self.n_gateways), keys)
                return taus, sel

            taus, sel = jax.jit(jax.vmap(run_v))(v_values)
            return np.asarray(taus), np.asarray(sel)
