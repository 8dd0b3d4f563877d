"""The comparison that decides ``correct``: what the timed path produced
against the plain reference (``bench/reference``), number by number.

Each function takes what a load generator kept of the program's output
and returns ``{name: value}``; the limits live in
``bench/limits/<cell>.json``. The reference is teacher-forced where the
program's own output is an input of the next step: each round's decision
is solved again from the queues the program entered that round with, and
the reference trains the gateways the program trained. Every pick is
itself compared, by the drift-plus-penalty objective it reaches, so a pick
that ties the reference's is sound and any other is caught.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import numpy as np

TINY = 1e-30


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), TINY)


def reference_deployment(config: dict):
    """The reference's deployment for ``config`` (its deployment seed)."""
    from bench.reference import control_plane as cp
    from bench.reference import vgg_split_fl as ref
    sc = config["scenario"]
    return ref.deployment(int(config["deployment_seed"]),
                          cp.NetworkConfig(**config["net"]),
                          width_mult=sc["width_mult"], classes=sc["classes"],
                          alpha=sc["alpha"], max_dataset=sc["max_dataset"],
                          chi=sc["chi"], k_iters=sc["k_iters"])


def resolve(dec, net, n_devices: int):
    """Trained gateways (selected, feasible, finite delay), the (N,) cuts
    and the realized delay of a reference decision."""
    trained, l_n, delays = [], np.zeros(n_devices, int), []
    for m in np.where(dec.selected)[0]:
        j = int(np.argmax(dec.assignment[m]))
        sol = dec.solutions.get((int(m), j))
        if sol is None or not sol.feasible or not np.isfinite(sol.delay):
            continue
        trained.append(int(m))
        delays.append(float(sol.delay))
        for i, dev in enumerate(net.devices_of(m)):
            l_n[dev] = int(sol.l_split[i])
    return trained, l_n, max(delays, default=0.0)


def objective_gap(v: float, queues: np.ndarray, sel_p, tau_p, sel_r,
                  tau_r) -> float:
    """|P2 objective (V tau - sum Q 1) of the program's pick - that of the
    reference's| over the size of the objective's terms."""
    obj_p = v * tau_p - float(np.sum(queues * np.asarray(sel_p, float)))
    obj_r = v * tau_r - float(np.sum(queues * np.asarray(sel_r, float)))
    scale = v * max(abs(tau_r), abs(tau_p)) + float(np.sum(np.abs(queues)))
    return abs(obj_p - obj_r) / max(scale, TINY)


@dataclasses.dataclass
class DecideGaps:
    queue_gap: float = 0.0
    dpp_gap: float = 0.0
    tau_gap: float = 0.0
    mismatch: int = 0          # trained sets or cuts apart under equal picks

    def merge(self, o: "DecideGaps") -> "DecideGaps":
        return DecideGaps(max(self.queue_gap, o.queue_gap),
                          max(self.dpp_gap, o.dpp_gap),
                          max(self.tau_gap, o.tau_gap),
                          self.mismatch + o.mismatch)


def _queue_step_gap(q_in, sel, gamma, q_out) -> float:
    """How far the program's post-round queues are from Eq. (14) applied to
    its own pre-round queues, pick and participation rates."""
    from bench.reference import control_plane as cp
    want = cp.update_queues(np.asarray(q_in, np.float64),
                            np.asarray(sel, bool), gamma)
    return float(np.max(np.abs(np.asarray(q_out, np.float64) - want)))


def ddsra_lane(dep, states, gamma_p, v: float, sel_p, tau_p, queues_p,
               trained_p=None, cuts_p=None,
               rounds: Optional[Sequence[int]] = None) -> DecideGaps:
    """Check one DDSRA lane round by round. ``sel_p (T, M)``, ``tau_p
    (T,)`` and ``queues_p (T, M)`` (post-round) are the program's;
    ``trained_p`` (T lists) and ``cuts_p (T, N)`` where it reports them.
    The reference solves round t (for t in ``rounds``, all by default) from
    that round's channel draw and the queues the program entered it with;
    every round's queue update is checked against Eq. (14) with the
    program's participation rates ``gamma_p``."""
    from bench.reference import control_plane as cp
    T = len(tau_p)
    todo = set(range(T)) if rounds is None else set(rounds)
    n_dev = dep.net.cfg.n_devices
    q = np.zeros(dep.net.cfg.n_gateways)
    g = DecideGaps()
    for t in range(T):
        if t in todo:
            dec = cp.ddsra_round(dep.workload, dep.net, states[t], q,
                                 gamma_p, v)
            tr_r, l_r, tau_r = resolve(dec, dep.net, n_dev)
            g.dpp_gap = max(g.dpp_gap, objective_gap(
                v, q, sel_p[t], tau_p[t], dec.selected, tau_r))
            if np.array_equal(np.asarray(sel_p[t], bool), dec.selected):
                g.tau_gap = max(g.tau_gap, _rel(float(tau_p[t]), tau_r))
                if trained_p is not None:
                    if sorted(trained_p[t]) != tr_r:
                        g.mismatch += 1
                    else:
                        devs = [int(n) for m in tr_r
                                for n in dep.net.devices_of(m)]
                        g.mismatch += int(np.sum(
                            np.asarray(cuts_p[t])[devs] != l_r[devs]))
        g.queue_gap = max(g.queue_gap,
                          _queue_step_gap(q, sel_p[t], gamma_p, queues_p[t]))
        q = np.asarray(queues_p[t], np.float64)
    return g


def baseline_lane(dep, states, gamma_p, picks, sel_p, tau_p, queues_p,
                  delay_driven: bool = False) -> DecideGaps:
    """Check one fixed-resource baseline lane round by round: ``picks (T,
    J)`` are the reference's own (round robin, random), or ``None`` for the
    delay-driven greedy pick. A pick that differs counts in ``mismatch``."""
    from bench.reference import control_plane as cp
    q = np.zeros(dep.net.cfg.n_gateways)
    g = DecideGaps()
    for t in range(len(tau_p)):
        ctx = cp.RoundContext(t, dep.workload, dep.net, states[t], q,
                              gamma_p, 0.0)
        chosen = cp.delay_driven_pick(ctx) if delay_driven else picks[t]
        dec = cp._decision_for(ctx, chosen)
        _, _, tau_r = resolve(dec, dep.net, dep.net.cfg.n_devices)
        if not np.array_equal(np.asarray(sel_p[t], bool), dec.selected):
            g.mismatch += 1
        else:
            g.tau_gap = max(g.tau_gap, _rel(float(tau_p[t]), tau_r))
        g.queue_gap = max(g.queue_gap,
                          _queue_step_gap(q, sel_p[t], gamma_p, queues_p[t]))
        q = np.asarray(queues_p[t], np.float64)
    return g


def draw_states(dep, run_seed: int, rounds: int) -> List:
    """The channel trajectory of a run under ``run_seed``: the deployment
    seed continues the topology's stream, any other seed restarts it."""
    net = dep.net
    saved = net.rng
    if run_seed == dep.seed:
        rng = np.random.default_rng()
        rng.bit_generator.state = dep.net_rng_state0
    else:
        rng = np.random.default_rng(run_seed)
    net.rng = rng
    try:
        return [net.draw() for _ in range(rounds)]
    finally:
        net.rng = saved


def _leaf_deltas(p0, p1) -> list:
    import jax
    return [np.asarray(y, np.float64) - np.asarray(x, np.float64)
            for x, y in zip(jax.tree.leaves(p0), jax.tree.leaves(p1))]


def _moved(dr: list) -> tuple:
    """The reference's change norm per leaf, the median leaf's, and which
    leaves count: those the reference moves by a thousandth of the median
    leaf's change or more (a leaf whose gradient is nought to rounding
    moves by round-off alone)."""
    nr = [float(np.linalg.norm(d)) for d in dr]
    med = float(np.median(nr))
    return nr, med, [n >= 1e-3 * med for n in nr]


def leaf_update_gaps(p0, p1, r0, r1) -> tuple:
    """Per leaf, |norm of the program's change - norm of the reference's|
    over the larger of that leaf's reference norm and the median leaf's.
    Returns (worst leaf, median leaf) over the leaves that count."""
    dp, dr = _leaf_deltas(p0, p1), _leaf_deltas(r0, r1)
    nr, med, keep = _moved(dr)
    gaps = [abs(float(np.linalg.norm(a)) - b) / max(b, med, TINY)
            for a, b, k in zip(dp, nr, keep) if k]
    return float(max(gaps)), float(np.median(gaps))


def leaf_vector_gap(p0, p1, r0, r1) -> float:
    """The worst leaf's ||program's change - reference's change|| over the
    larger of that leaf's reference norm and the median leaf's, over the
    leaves that count: the direction of the update, which a norm alone
    does not see."""
    dp, dr = _leaf_deltas(p0, p1), _leaf_deltas(r0, r1)
    nr, med, keep = _moved(dr)
    return float(max(float(np.linalg.norm(a - b)) / max(n, med, TINY)
                     for a, b, n, k in zip(dp, dr, nr, keep) if k))


def params_like(ref_tree, prog_tree):
    """The program's parameters in the reference's tree, leaf by leaf; the
    two layouts have to agree in every shape."""
    import jax
    import jax.numpy as jnp
    leaves, tdef = jax.tree.flatten(ref_tree)
    prog = jax.tree.leaves(prog_tree)
    if [np.shape(a) for a in prog] != [np.shape(a) for a in leaves]:
        raise ValueError("the program's parameters differ in layout from "
                         "the reference's")
    return jax.tree.unflatten(tdef, [jnp.asarray(a, jnp.float32)
                                     for a in prog])


def _stat_gap(p, r) -> float:
    p, r = np.asarray(p, np.float64), np.asarray(r, np.float64)
    return float(np.max(np.abs(p - r) / np.maximum(np.abs(r), TINY)))


def fused_numbers(config: dict, traffic: dict, seed: int, kept: dict, *,
                  half_batch: bool = False, stale_eval: bool = False,
                  parts: Sequence[str] = ("stats", "decide", "train")
                  ) -> Dict[str, float]:
    """Numbers of a fused cell. ``kept`` holds the program's output of the
    first ``check_calls`` blocks: ``gamma`` and ``stats`` (sigma, delta,
    lipschitz), ``records`` (per round: selected, trained, l_n, delay,
    queues, losses, accuracy), and ``params`` (host copies before the first
    block and after each). Names ending in ``.info`` are readings kept for
    the record and compared with nothing. ``parts`` picks the layers to
    compare; ``half_batch`` and ``stale_eval`` plant those faults in the
    reference (``bench/calibrate.py`` reads them): each device trains on
    the first half of its batch, or the eval is counted on the weights the
    block started from.

    The data plane is compared two ways. Free-running, the reference
    trains from its own initial weights for all the checked rounds, with
    the program's trained gateways: the worst leaf's gap of update norms
    after the first block and after the last, the loss gap of round 0
    (both sides start it from the same weights) and the worst leaf's gap
    of update vectors over the first block. Teacher-forced, each later
    block starts from the weights the program entered it with, as the
    decide layer starts from the program's queues; its first round's loss
    gap and its update-vector gap are readings. The in-scan eval of a
    block's last round is counted again on the weights the program kept
    there."""
    import jax
    from bench.reference import vgg_split_fl as ref
    sc = config["scenario"]
    dep = reference_deployment(config)
    # the statistics come from the deployment seed's weights, training
    # starts from the run seed's
    kinds, stats_params = ref.init_vgg11(jax.random.PRNGKey(dep.seed),
                                         sc["width_mult"], sc["classes"])
    _, params0 = ref.init_vgg11(jax.random.PRNGKey(seed), sc["width_mult"],
                                sc["classes"])
    out: Dict[str, float] = {}
    if "stats" in parts:
        gamma, stats = ref.participation(
            dep, kinds, stats_params, lr=sc["lr"], k_iters=sc["k_iters"],
            sigma_samples=sc["sigma_samples"],
            n_channels=config["net"]["n_channels"], half_batch=half_batch)
        ps = kept["stats"]
        out["stats.sigma_gap"] = _stat_gap(ps["sigma"], stats.sigma)
        out["stats.delta_gap"] = _stat_gap(ps["delta"], stats.delta)
        out["stats.lipschitz_gap.info"] = _stat_gap(ps["lipschitz"],
                                                    stats.lipschitz)
        out["stats.gamma_gap.info"] = _stat_gap(kept["gamma"], gamma)

    recs = kept["records"]
    if "decide" in parts:
        states = draw_states(dep, seed, len(recs))
        g = ddsra_lane(dep, states, np.asarray(kept["gamma"], np.float64),
                       float(traffic["v"]), [r["selected"] for r in recs],
                       [r["delay"] for r in recs],
                       [np.asarray(r["queues"]) for r in recs],
                       trained_p=[r["trained"] for r in recs],
                       cuts_p=[r["l_n"] for r in recs])
        out["decide.queue_gap"] = g.queue_gap
        out["decide.dpp_gap"] = g.dpp_gap
        out["decide.tau_gap"] = g.tau_gap
        out["decide.mismatch"] = float(g.mismatch)
    if "train" not in parts:
        return out

    local_train = ref.make_local_train(kinds, lr=sc["lr"],
                                       k_iters=sc["k_iters"])
    hits = ref.make_hits(kinds)
    rpc = int(traffic["rounds_per_call"])
    n_test = len(dep.y_test)
    scale = float(np.log(sc["classes"]))      # the untrained model's loss
    p = kept["params"]

    def rounds(params, t0):
        """The reference over one block's rounds from ``params``; returns
        its end weights and each round's {gateway: loss}."""
        losses = []
        for t in range(t0, min(t0 + rpc, len(recs))):
            params, gw = ref.fl_round(local_train, dep, seed, params, t,
                                      recs[t]["trained"],
                                      half_batch=half_batch)
            losses.append(gw)
        return jax.device_get(params), losses

    def loss_gaps(t, gw) -> list:
        return [abs(float(recs[t]["losses"][m]) - v) / scale
                for m, v in gw.items()]

    # free-running: the first block's run is also the first teacher-forced
    snaps, free_losses = [params0], []
    for b in range(len(p) - 1):
        end, gw = rounds(snaps[-1], b * rpc)
        snaps.append(end)
        free_losses += gw
    for name, i in (("first", 1), ("last", len(p) - 1)):
        worst, med = leaf_update_gaps(p[0], p[i], snaps[0], snaps[i])
        out[f"train.update_gap.{name}"] = worst
        out[f"train.update_gap.{name}.median"] = med
    # round 0 starts from the same weights on both sides: the first local
    # steps' loss, before training has compounded any rounding
    for t, gw in enumerate(free_losses):
        out[f"train.loss_gap.r{t}" + (".info" if t else "")] = max(
            loss_gaps(t, gw), default=0.0)

    vec, hits_gap = [], []
    for b in range(len(p) - 1):
        t0 = b * rpc
        if b == 0:
            start, end, gw = snaps[0], snaps[1], free_losses[:rpc]
        else:
            start = params_like(params0, p[b])
            end, gw = rounds(start, t0)
        vec.append(leaf_vector_gap(p[b], p[b + 1], start, end))
        if b:
            out[f"train.loss_gap.first_round.b{b}.info"] = max(
                loss_gaps(t0, gw[0]) if gw else [], default=0.0)
        acc = recs[t0 + rpc - 1]["accuracy"] if t0 + rpc <= len(recs) \
            else None
        if acc is not None:
            at = p[b] if stale_eval else p[b + 1]
            h = int(hits(params_like(params0, at), dep.x_test, dep.y_test))
            hits_gap.append(abs(round(float(acc) * n_test) - h) / n_test)
        out[f"train.update_vec_gap.b{b}.info"] = vec[-1]
    # the first block's: the later blocks start from trained weights, on
    # which the default-precision passes compound into loss and direction
    # gaps as wide as the faults' (PERF.md)
    out["train.update_vec_gap.first"] = vec[0]
    out["eval.hits_gap"] = max(hits_gap, default=0.0)
    return out


def grid_numbers(config: dict, traffic: dict, seed: int, kept: dict
                 ) -> Dict[str, float]:
    """Numbers of a policy-grid cell. ``kept`` holds ``gamma``, the first
    call's ``taus``, ``selected``, ``queues`` ((P, S, V, T[, M])) and
    ``repeat_mismatch``, the count of window calls whose output differed
    from the first's. Baseline lanes are checked whole; of the DDSRA
    lanes, ``traffic["check"]["ddsra_answers"]`` (lane, round) solves drawn
    from the seed are made again, and every round's queue update is
    checked."""
    from bench.reference import control_plane as cp
    dep = reference_deployment(config)
    gamma = np.asarray(kept["gamma"], np.float64)
    policies = traffic["policies"]
    seeds = [seed + int(o) for o in traffic["seed_offsets"]]
    v_values = [float(v) for v in traffic["v_values"]]
    T = int(traffic["rounds"])
    taus, sel, qs = kept["taus"], kept["selected"], kept["queues"]
    m_gw, j_ch = dep.net.cfg.n_gateways, dep.net.cfg.n_channels
    states = {s: draw_states(dep, s, T) for s in seeds}

    rng = np.random.default_rng(seed)
    lanes = [(pi, si, vi) for pi, p in enumerate(policies)
             if p == "ddsra_jax" for si in range(len(seeds))
             for vi in range(len(v_values))]
    n_ans = min(int(traffic["check"]["ddsra_answers"]), len(lanes) * T)
    todo: Dict = {}
    for f in rng.choice(len(lanes) * T, size=n_ans, replace=False):
        todo.setdefault(lanes[f // T], []).append(int(f % T))

    g = DecideGaps()
    for pi, p in enumerate(policies):
        for si, s in enumerate(seeds):
            if p == "ddsra_jax":
                for vi, v in enumerate(v_values):
                    g = g.merge(ddsra_lane(
                        dep, states[s], gamma, v, sel[pi, si, vi],
                        taus[pi, si, vi], qs[pi, si, vi],
                        rounds=todo.get((pi, si, vi), [])))
                continue
            if p == "round_robin":
                picks = [cp.round_robin_pick(t, m_gw, j_ch) for t in range(T)]
            elif p == "random":
                picks = cp.random_picks(s, T, m_gw, j_ch)
            else:
                picks = None
            lane = baseline_lane(dep, states[s], gamma, picks,
                                 sel[pi, si, 0], taus[pi, si, 0],
                                 qs[pi, si, 0],
                                 delay_driven=p == "delay_driven")
            # baselines ignore V: every V row must repeat the first
            for vi in range(1, len(v_values)):
                same = all(np.array_equal(a[pi, si, vi], a[pi, si, 0])
                           for a in (sel, taus, qs))
                lane.mismatch += 0 if same else 1
            g = g.merge(lane)
    return {"decide.queue_gap": g.queue_gap,
            "decide.dpp_gap": g.dpp_gap,
            "decide.tau_gap": g.tau_gap,
            "decide.mismatch": float(g.mismatch),
            "grid.repeat_mismatch": float(kept["repeat_mismatch"])}
