"""Plain numpy reference of the DDSRA control plane and the fixed-resource
baselines (paper Sec. III-V, Algorithm 1), frozen with the benchmark.

A verbatim copy of the host-side oracle the program was modelled on: the
wireless/energy model, the Table II cost model for VGG-11, the partition,
frequency and power solves, the Hungarian channel assignment, the
Lyapunov queue update, the participation rates of Eq. (13), and the
round-robin / random / delay-driven baselines. It imports nothing of the
program, so the comparison that decides ``correct`` keeps its meaning
whatever later changes do to the program.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

_PSI = 1e18     # "extremely large positive value" in (29)


# ---------------------------------------------------------------------------
# network (wireless channel + energy model, Sec. III-C)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class NetworkConfig:
    n_gateways: int = 6
    n_devices: int = 12
    n_channels: int = 3
    # channel
    h0_db: float = -30.0          # path loss constant
    d0: float = 1.0               # reference distance (m)
    nu: float = 2.0               # path-loss exponent
    bandwidth_up: float = 1e6     # B^u (Hz)
    bandwidth_down: float = 20e6  # B^d (Hz)
    noise_psd_dbm: float = -174.0 # N0 (dBm/Hz)
    p_bs: float = 1.0             # BS transmit power (W)
    p_max: float = 0.2            # gateway max transmit power (W)
    # the paper only says interference is Gaussian "with different variances";
    # chosen here to sit near the thermal noise floor so SINRs land in the
    # 10-30 dB regime the paper's delays imply
    interference_up_var: float = 1e-26
    interference_down_var: float = 1e-25
    # energy
    e_dev_max: float = 5.0        # J per round (uniform arrival bound)
    e_gw_max: float = 30.0
    v_dev: float = 1e-27          # effective switched capacitance
    v_gw: float = 1e-27
    # compute
    phi_dev: float = 16.0         # FLOPs / cycle
    phi_gw: float = 32.0
    f_dev_range: tuple = (0.1e9, 1.0e9)
    f_gw_max: float = 4.0e9
    f_gw_min: float = 0.1e9
    # memory (bytes)
    g_dev_max: float = 2e9
    g_gw_max: float = 4e9
    dist_range: tuple = (1000.0, 2000.0)


@dataclasses.dataclass
class ChannelState:
    """Per-round draw: gains/interference for every (gateway, channel)."""
    h_up: np.ndarray       # (M, J)
    h_down: np.ndarray     # (M, J)
    i_up: np.ndarray       # (M, J)
    i_down: np.ndarray     # (M, J)
    e_dev: np.ndarray      # (N,) energy arrivals
    e_gw: np.ndarray       # (M,)


class Network:
    def __init__(self, cfg: NetworkConfig, rng: Optional[np.random.Generator] = None):
        self.cfg = cfg
        self.rng = rng or np.random.default_rng(0)
        self.h0 = 10 ** (cfg.h0_db / 10)
        self.n0 = 10 ** (cfg.noise_psd_dbm / 10) / 1000.0   # W/Hz
        # static deployment
        self.dist = self.rng.uniform(*cfg.dist_range, size=cfg.n_gateways)
        self.f_dev = self.rng.uniform(*cfg.f_dev_range, size=cfg.n_devices)
        # devices -> gateways round-robin (2 per gateway in the paper setup)
        self.assign = np.arange(cfg.n_devices) % cfg.n_gateways
        self.a = np.zeros((cfg.n_devices, cfg.n_gateways))
        self.a[np.arange(cfg.n_devices), self.assign] = 1.0

    def devices_of(self, m: int) -> np.ndarray:
        return np.where(self.assign == m)[0]

    def draw(self) -> ChannelState:
        cfg, rng = self.cfg, self.rng
        m, j = cfg.n_gateways, cfg.n_channels
        path = self.h0 * (cfg.d0 / self.dist[:, None]) ** cfg.nu
        h_up = path * rng.exponential(1.0, size=(m, j))
        h_down = path * rng.exponential(1.0, size=(m, j))
        i_up = np.abs(rng.normal(0, np.sqrt(cfg.interference_up_var), (m, j)))
        i_down = np.abs(rng.normal(0, np.sqrt(cfg.interference_down_var), (m, j)))
        e_dev = rng.uniform(0, cfg.e_dev_max, cfg.n_devices)
        e_gw = rng.uniform(0, cfg.e_gw_max, cfg.n_gateways)
        return ChannelState(h_up, h_down, i_up, i_down, e_dev, e_gw)

    # rates / delays / energies -------------------------------------------------

    def uplink_rate(self, m: int, j: int, p: float, st: ChannelState) -> float:
        cfg = self.cfg
        sinr = p * st.h_up[m, j] / (cfg.bandwidth_up * self.n0 + st.i_up[m, j])
        return cfg.bandwidth_up * np.log2(1.0 + sinr)

    def downlink_rate(self, m: int, j: int, st: ChannelState) -> float:
        cfg = self.cfg
        sinr = cfg.p_bs * st.h_down[m, j] / (cfg.bandwidth_down * self.n0 + st.i_down[m, j])
        return cfg.bandwidth_down * np.log2(1.0 + sinr)

    def uplink_time(self, m: int, j: int, p: float, gamma: float, st: ChannelState) -> float:
        """Eq. (7): model upload time."""
        r = self.uplink_rate(m, j, p, st)
        return np.inf if r <= 0 else gamma * 8.0 / r

    def downlink_time(self, m: int, j: int, gamma: float, st: ChannelState) -> float:
        """Eq. (6)."""
        r = self.downlink_rate(m, j, st)
        return np.inf if r <= 0 else gamma * 8.0 / r

    def uplink_energy(self, m: int, j: int, p: float, gamma: float, st: ChannelState) -> float:
        """Eq. (8)."""
        return p * self.uplink_time(m, j, p, gamma, st)



# ---------------------------------------------------------------------------
# Table II cost model (VGG-11)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerCost:
    name: str
    kind: str
    flops_fwd: float          # o_l, per sample point
    flops_bwd: float          # o'_l, per sample point
    mem_weights: float        # bytes (incl. gradient buffers where Table II says so)
    mem_act_per_sample: float # bytes per sample (fwd outputs + bwd errors)
    sf: float = 4.0           # native precision, bytes/param (S_f in Table II)

    def flops(self) -> float:
        return self.flops_fwd + self.flops_bwd

    def mem(self, batch: int) -> float:
        return self.mem_weights + batch * self.mem_act_per_sample


# ---------------------------------------------------------------------------
# Table II entries (verbatim). S_f = precision bytes.
# ---------------------------------------------------------------------------


def conv_layer(name: str, ci: int, hi: int, wi: int, co: int,
               hf: int = 3, wf: int = 3, stride: int = 1, pad: int = 1,
               sf: int = 4) -> LayerCost:
    ho = (hi + 2 * pad - hf) // stride + 1
    wo = (wi + 2 * pad - wf) // stride + 1
    fwd = 2 * ci * hf * wf * co * ho * wo                       # B_s = 1
    err = 2 * (2 * wf + wf * wo - 2) * (2 * hf + hf * ho - 2)
    grad = 2 * ci * hf * wf * co * ho * wo
    weights = sf * ci * hf * wf * co
    acts = sf * (co * ho * wo + ci * hi * wi)                   # fwd out + bwd err
    return LayerCost(name, "conv", fwd, err + grad,
                     2 * weights,                               # weight + gradient
                     acts, sf=sf)


def pool_layer(name: str, ci: int, hi: int, wi: int, k: int = 2,
               sf: int = 4) -> LayerCost:
    ho, wo = hi // k, wi // k
    fwd = ci * hi * wi
    err = ci * hi * wi
    acts = sf * (ci * ho * wo + ci * hi * wi)
    return LayerCost(name, "pool", fwd, err, 0.0, acts, sf=sf)


def fc_layer(name: str, si: int, so: int, sf: int = 4) -> LayerCost:
    fwd = 2 * si * so
    bwd = 2 * si * so + si * so                                 # error + gradient
    weights = sf * si * so
    acts = sf * (so + si)
    return LayerCost(name, "fc", fwd, bwd, 2 * weights, acts, sf=sf)


# ---------------------------------------------------------------------------
# VGG-11 (the paper's experiment DNN), 32x32x3 inputs (SVHN / CIFAR-10)
# ---------------------------------------------------------------------------

VGG11_PLAN = [64, "M", 128, "M", 256, 256, "M", 512, 512, "M", 512, 512, "M"]


def vgg11_layers(width_mult: float = 1.0, sf: int = 4,
                 image: int = 32, classes: int = 10) -> List[LayerCost]:
    layers: List[LayerCost] = []
    ci, hw = 3, image
    idx = 0
    for item in VGG11_PLAN:
        if item == "M":
            layers.append(pool_layer(f"pool{idx}", ci, hw, hw, sf=sf))
            hw //= 2
        else:
            co = max(1, int(item * width_mult))
            layers.append(conv_layer(f"conv{idx}", ci, hw, hw, co, sf=sf))
            ci = co
            idx += 1
    feat = ci * hw * hw
    fc1 = max(16, int(4096 * width_mult))
    layers.append(fc_layer("fc0", feat, fc1, sf=sf))
    layers.append(fc_layer("fc1", fc1, fc1, sf=sf))
    layers.append(fc_layer("fc2", fc1, classes, sf=sf))
    return layers


def flops_vector(layers: Sequence[LayerCost]) -> np.ndarray:
    """(o_l + o'_l) per layer."""
    return np.array([l.flops() for l in layers], float)


def mem_vector(layers: Sequence[LayerCost], batch: int) -> np.ndarray:
    """g_l per layer at training batch size."""
    return np.array([l.mem(batch) for l in layers], float)


def model_size_bytes(layers: Sequence[LayerCost]) -> float:
    """gamma: DNN model size transmitted between tiers (weights only)."""
    return float(sum(l.mem_weights / 2 for l in layers))  # /2: exclude grad buffer



# ---------------------------------------------------------------------------
# Hungarian method, Lyapunov queues, participation rates
# ---------------------------------------------------------------------------

def hungarian_min(cost: np.ndarray) -> Tuple[np.ndarray, float]:
    """Min-cost assignment of rows to columns.

    cost: (R, C) with R <= C. Returns (col_of_row (R,), total_cost).
    """
    cost = np.asarray(cost, float)
    r, c = cost.shape
    assert r <= c, "rows must be <= cols (pad the caller otherwise)"
    INF = 1e30
    u = np.zeros(r + 1)
    v = np.zeros(c + 1)
    p = np.zeros(c + 1, dtype=int)      # p[col] = row matched to col (1-based)
    way = np.zeros(c + 1, dtype=int)

    for i in range(1, r + 1):
        p[0] = i
        j0 = 0
        minv = np.full(c + 1, INF)
        used = np.zeros(c + 1, dtype=bool)
        while True:
            used[j0] = True
            i0 = p[j0]
            free = ~used[1:]                      # candidate columns 1..c
            # relax all free columns against row i0 at once
            cur = cost[i0 - 1, :] - u[i0] - v[1:]
            better = free & (cur < minv[1:])
            minv[1:] = np.where(better, cur, minv[1:])
            way[1:] = np.where(better, j0, way[1:])
            # masked argmin picks the next column to add to the tree
            masked = np.where(free, minv[1:], INF)
            j1 = int(np.argmin(masked)) + 1
            delta = masked[j1 - 1]
            # update potentials (matched rows of used columns are distinct)
            used_j = np.flatnonzero(used)
            u[p[used_j]] += delta
            v[used_j] -= delta
            minv[1:] = np.where(free, minv[1:] - delta, minv[1:])
            j0 = j1
            if p[j0] == 0:
                break
        while j0:
            j1 = way[j0]
            p[j0] = p[j1]
            j0 = j1

    col_of_row = np.full(r, -1, dtype=int)
    for j in range(1, c + 1):
        if p[j] > 0:
            col_of_row[p[j] - 1] = j - 1
    total = float(cost[np.arange(r), col_of_row].sum())
    return col_of_row, total


def assign_channels(theta: np.ndarray) -> np.ndarray:
    """Solve (28): theta (M, J) costs; returns I (M, J) in {0,1}.

    Channels are rows (each channel must be used exactly once, C3); gateways
    are columns (at most one channel each, C2). Requires J <= M.
    """
    m, j = theta.shape
    assert j <= m, "need at least as many gateways as channels"
    col_of_row, _ = hungarian_min(theta.T)     # (J,) gateway per channel
    eye = np.zeros((m, j))
    for ch, gw in enumerate(col_of_row):
        eye[gw, ch] = 1.0
    return eye


def update_queues(q: np.ndarray, selected: np.ndarray, gamma: np.ndarray) -> np.ndarray:
    """Eq. (14): Q_m(t+1) = max(Q_m(t) - 1_m^t + Gamma_m, 0)."""
    return np.maximum(q - selected.astype(float) + gamma, 0.0)


@dataclasses.dataclass
class DataStats:
    """Per-device statistics estimated from the training process."""
    sigma: np.ndarray    # (N,) per-sample gradient variance bound
    delta: np.ndarray    # (N,) local-vs-global gradient divergence
    lipschitz: np.ndarray  # (N,) smoothness constants L_n
    d_tilde: np.ndarray  # (N,) training batch sizes


def divergence_bound(stats: DataStats, assign: np.ndarray,
                     beta: float, k_epochs: int) -> np.ndarray:
    """Phi_m per gateway. assign: (N,) device -> gateway index."""
    n = len(stats.sigma)
    m = int(assign.max()) + 1
    phi = np.zeros(m)
    for g in range(m):
        devs = np.where(assign == g)[0]
        w = stats.d_tilde[devs]
        w = w / w.sum()
        term = (stats.sigma[devs] / (stats.lipschitz[devs] * np.sqrt(stats.d_tilde[devs]))
                + stats.delta[devs] / stats.lipschitz[devs])
        growth = (beta * stats.lipschitz[devs] + 1.0) ** k_epochs - 1.0
        phi[g] = float(np.sum(w * term * growth))
    return phi


def participation_rates(phi: np.ndarray, n_channels: int) -> np.ndarray:
    """Eq. (13). Gateways with smaller divergence get larger Gamma_m."""
    inv = 1.0 / np.maximum(phi, 1e-12)
    gamma = n_channels * inv / inv.sum()
    return np.minimum(gamma, 1.0)



# ---------------------------------------------------------------------------
# DDSRA (Algorithm 1)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Workload:
    """Layer-level training workload (from repro.core.costmodel)."""
    flops: np.ndarray        # (L,) o_l + o'_l per sample
    mem: np.ndarray          # (L,) g_l bytes (training batch already folded in)
    gamma: float             # model size, bytes
    k_iters: int             # local epochs K
    d_tilde: np.ndarray      # (N,) training batch sizes

    @property
    def n_layers(self) -> int:
        return len(self.flops)


@dataclasses.dataclass
class GatewaySolution:
    feasible: bool
    delay: float                   # Lambda_{m,j}
    l_split: np.ndarray            # per associated device
    f_gw: np.ndarray               # per associated device (Hz)
    p_tx: float
    e_dev: np.ndarray
    e_gw: float


@dataclasses.dataclass
class RoundDecision:
    """One round's schedule plus the policy's post-decision queue state.

    ``queues`` contract: it must be the Eq. (14) update of the pre-decision
    queues under the *scheduled* indicator ``selected``. Synchronous
    engines apply it verbatim. Under ``engine="async"`` realized
    participation can diverge from the schedule (churn, stragglers landing
    late), and when it does the simulation *discards* ``queues`` and redoes
    Eq. (14) from the pre-decision queues with the realized indicator
    (``lyapunov.update_queues_realized``) — a policy encoding a different
    queue law in ``queues`` would be silently overridden on exactly those
    rounds, so custom non-Eq.-(14) queue dynamics are only honored on
    synchronous engines (or fault-free async rounds).
    """
    assignment: np.ndarray         # I (M, J)
    selected: np.ndarray           # (M,) bool
    lam: np.ndarray                # (M, J) Lambda
    solutions: dict                # (m, j) -> GatewaySolution
    delay: float                   # tau(t), Eq. (10)
    queues: np.ndarray             # post-update virtual queues


# ---------------------------------------------------------------------------
# inner solvers for one (gateway, channel)
# ---------------------------------------------------------------------------


def _cum(front: np.ndarray) -> np.ndarray:
    """cumulative sums with a leading 0: cum[l] = sum of first l entries."""
    return np.concatenate([[0.0], np.cumsum(front)])


def _train_times(w: Workload, devs: np.ndarray, l: np.ndarray, f_dev: np.ndarray,
                 phi_dev: float, phi_gw: float, f_gw: np.ndarray) -> np.ndarray:
    cumf = _cum(w.flops)
    tot = cumf[-1]
    bottom = cumf[l]
    top = tot - bottom
    with np.errstate(divide="ignore"):
        t_dev = bottom / (phi_dev * f_dev)
        t_gw = np.where(top > 0, top / np.maximum(phi_gw * f_gw, 1e-9), 0.0)
    return w.k_iters * w.d_tilde[devs] * (t_dev + t_gw)


def solve_partition(w: Workload, net: Network, m: int, devs: np.ndarray,
                    f_gw: np.ndarray, st: ChannelState,
                    e_gw_budget: float, iters: int = 40) -> Optional[np.ndarray]:
    """Bisection on eta for sub-problem (21). Returns l (per device) or None."""
    cfg = net.cfg
    cumf, cumg = _cum(w.flops), _cum(w.mem)
    tot_f, tot_g = cumf[-1], cumg[-1]
    f_dev = net.f_dev[devs]
    n_loc = len(devs)
    big_l = w.n_layers

    kd = w.k_iters * w.d_tilde[devs]

    # per-device static upper bounds from C7' (memory) and C10' (energy),
    # all devices at once on the (n_loc, L+1) grid
    mem_ok = cumg <= cfg.g_dev_max                              # (L+1,)
    e_grid = (kd * cfg.v_dev / cfg.phi_dev * f_dev ** 2)[:, None] * cumf[None, :]
    ok_static = mem_ok[None, :] & (e_grid <= st.e_dev[devs][:, None])
    if not ok_static.any(axis=1).all():
        return None
    hi_static = big_l - np.argmax(ok_static[:, ::-1], axis=1)

    # per-device time at every cut, hoisted out of the bisection: (n_loc, L+1)
    t_grid = kd[:, None] * (
        cumf[None, :] / (cfg.phi_dev * f_dev)[:, None]
        + (tot_f - cumf[None, :]) / np.maximum(cfg.phi_gw * f_gw, 1e-9)[:, None])
    ls_ok_static = np.arange(big_l + 1)[None, :] <= hi_static[:, None]
    gw_e_coef = kd * cfg.v_gw / cfg.phi_gw * f_gw ** 2

    def feasible(eta: float) -> Optional[np.ndarray]:
        """Largest l per device with time <= eta (within static bounds),
        then check joint gateway constraints C8' and C9'."""
        ok = (t_grid <= eta) & ls_ok_static
        if not ok.any(axis=1).all():
            return None
        # prefer the largest l meeting eta: minimizes gateway load (C8'/C9')
        l_pick = big_l - np.argmax(ok[:, ::-1], axis=1)
        if np.sum(tot_g - cumg[l_pick]) > cfg.g_gw_max:
            return None
        if np.sum(gw_e_coef * (tot_f - cumf[l_pick])) > e_gw_budget:
            return None
        return l_pick

    lo = 0.0
    hi = float(np.max(w.k_iters * w.d_tilde[devs]) * tot_f
               / min(cfg.phi_dev * f_dev.min(), cfg.phi_gw * max(f_gw.min(), 1e-9)))
    best = feasible(hi)
    if best is None:
        return None
    for _ in range(iters):
        mid = 0.5 * (lo + hi)
        sol = feasible(mid)
        if sol is not None:
            hi, best = mid, sol
        else:
            lo = mid
    return best


def solve_frequency(w: Workload, net: Network, devs: np.ndarray, l: np.ndarray,
                    st: ChannelState, e_gw_budget: float,
                    iters: int = 40) -> Optional[np.ndarray]:
    """Bisection on theta for sub-problem (22)."""
    cfg = net.cfg
    cumf = _cum(w.flops)
    tot = cumf[-1]
    f_dev = net.f_dev[devs]
    dev_t = cumf[l] / (cfg.phi_dev * f_dev)              # per-sample device time
    gw_work = (tot - cumf[l]) / cfg.phi_gw               # cycles on gateway
    kd = w.k_iters * w.d_tilde[devs]

    if np.all(gw_work <= 0):
        return np.full(len(devs), cfg.f_gw_min / max(len(devs), 1))

    def f_of(theta: float) -> Optional[np.ndarray]:
        denom = theta / kd - dev_t
        if (denom <= 0).any():
            return None
        f = gw_work / denom
        f = np.maximum(f, 0.0)
        if f.sum() > cfg.f_gw_max:
            return None
        e = float(np.sum(kd * cfg.v_gw * gw_work * f ** 2))
        if e > e_gw_budget:
            return None
        return f

    lo = float(np.max(kd * (dev_t + gw_work / cfg.f_gw_max)))
    hi = float(np.max(kd * (dev_t + gw_work / max(cfg.f_gw_min / max(len(devs), 1), 1e3))))
    hi = max(hi, lo * 4 + 1.0)
    sol = f_of(hi)
    if sol is None:
        return None
    best = sol
    for _ in range(iters):
        mid = 0.5 * (lo + hi)
        s = f_of(mid)
        if s is not None:
            hi, best = mid, s
        else:
            lo = mid
    return best


def solve_power(net: Network, m: int, j: int, st: ChannelState, gamma: float,
                e_budget: float, iters: int = 60) -> float:
    """(23)/(24): largest transmit power whose upload energy fits e_budget."""
    cfg = net.cfg
    if e_budget <= 0:
        return 0.0
    if net.uplink_energy(m, j, cfg.p_max, gamma, st) <= e_budget:
        return cfg.p_max
    lo, hi = 0.0, cfg.p_max
    for _ in range(iters):
        mid = 0.5 * (lo + hi)
        if net.uplink_energy(m, j, mid, gamma, st) <= e_budget:
            lo = mid
        else:
            hi = mid
    return lo


def solve_gateway(w: Workload, net: Network, m: int, j: int, st: ChannelState,
                  bcd_iters: int = 4) -> GatewaySolution:
    """Full BCD for one (m, j): returns Lambda_{m,j} and the resources."""
    cfg = net.cfg
    devs = net.devices_of(m)
    n_loc = len(devs)
    infeasible = GatewaySolution(False, np.inf, np.zeros(n_loc, int),
                                 np.zeros(n_loc), 0.0, np.zeros(n_loc), 0.0)
    if n_loc == 0:
        return infeasible

    cumf = _cum(w.flops)
    tot = cumf[-1]
    f_gw = np.full(n_loc, cfg.f_gw_max / n_loc)
    p_tx = cfg.p_max
    l = None
    for _ in range(bcd_iters):
        e_up = net.uplink_energy(m, j, p_tx, w.gamma, st)
        e_budget = st.e_gw[m] - e_up
        l_new = solve_partition(w, net, m, devs, f_gw, st, e_budget)
        if l_new is None:
            return infeasible
        l = l_new
        f_new = solve_frequency(w, net, devs, l, st, e_budget)
        if f_new is None:
            return infeasible
        f_gw = np.maximum(f_new, 1e3)
        e_tra_gw = float(np.sum(
            w.k_iters * w.d_tilde[devs] * cfg.v_gw / cfg.phi_gw
            * (tot - cumf[l]) * f_gw ** 2))
        p_tx = solve_power(net, m, j, st, w.gamma, st.e_gw[m] - e_tra_gw)
        if p_tx <= 0:
            return infeasible

    t_train = float(np.max(_train_times(w, devs, l, net.f_dev[devs],
                                        cfg.phi_dev, cfg.phi_gw, f_gw)))
    t_up = net.uplink_time(m, j, p_tx, w.gamma, st)
    t_down = net.downlink_time(m, j, w.gamma, st)
    lam = t_train + t_up + t_down                       # Eq. (18)
    e_dev = (w.k_iters * w.d_tilde[devs] * cfg.v_dev / cfg.phi_dev
             * cumf[l] * net.f_dev[devs] ** 2)
    e_gw = e_tra_gw + net.uplink_energy(m, j, p_tx, w.gamma, st)
    return GatewaySolution(True, lam, l, f_gw, p_tx, e_dev, e_gw)


# ---------------------------------------------------------------------------
# per-round DDSRA step
# ---------------------------------------------------------------------------


def ddsra_round(w: Workload, net: Network, st: ChannelState, queues: np.ndarray,
                gamma_rates: np.ndarray, v: float) -> RoundDecision:
    cfg = net.cfg
    m_gw, j_ch = cfg.n_gateways, cfg.n_channels

    lam = np.full((m_gw, j_ch), np.inf)
    sols = {}
    for m in range(m_gw):                 # "do in parallel" in Algorithm 1
        for j in range(j_ch):
            sol = solve_gateway(w, net, m, j, st)
            sols[(m, j)] = sol
            lam[m, j] = sol.delay

    # channel assignment (26)-(31): sweep the lambda cap down the frontier of
    # distinct delay values, solving the Theta assignment (28)-(29) with the
    # Hungarian method at each cap, and keep the best P3 objective. This is
    # the paper's iterative lambda/I(t) solve, run to exhaustion (M*J caps).
    finite = np.isfinite(lam)
    best_eye, best_obj = None, None
    caps = np.unique(lam[finite])[::-1] if finite.any() else []
    for cap in caps:
        theta = np.where(finite & (lam <= cap + 1e-12),
                         -queues[:, None], _PSI)
        # a feasible assignment needs >=1 allowed gateway per channel
        if (theta >= _PSI).all(axis=0).any():
            continue
        eye = assign_channels(theta)
        if (np.where(eye > 0, theta, 0.0) >= _PSI).any():
            continue                       # Hungarian forced a banned pair
        tau = float(np.where(eye > 0, lam, -np.inf).max())
        obj = v * tau - float(np.sum(queues * eye.sum(axis=1)))
        if best_obj is None or obj < best_obj - 1e-12:
            best_obj, best_eye = obj, eye

    if best_eye is None:                   # nothing feasible this round
        best_eye = np.zeros((m_gw, j_ch))
    eye = best_eye
    selected = eye.sum(axis=1) > 0
    sel_lam = np.where(eye > 0, lam, -np.inf)
    tau = float(sel_lam.max()) if selected.any() else 0.0
    new_q = update_queues(queues, selected, gamma_rates)
    return RoundDecision(eye, selected, lam, sols, tau, new_q)


# ---------------------------------------------------------------------------
# fixed-resource baselines (Sec. VII-C)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RoundContext:
    t: int
    workload: Workload
    net: Network
    state: ChannelState
    queues: np.ndarray
    gamma_rates: np.ndarray        # participation-rate targets
    v: float

def _fixed_resource_solution(ctx: RoundContext, m: int, j: int,
                             l_frac: float = 0.5) -> GatewaySolution:
    """Evaluate a gateway at FIXED resources (baselines)."""
    net, st, w = ctx.net, ctx.state, ctx.workload
    cfg = net.cfg
    devs = net.devices_of(m)
    n_loc = len(devs)
    big_l = w.n_layers
    l = np.full(n_loc, int(round(l_frac * big_l)), dtype=int)
    f_gw = np.full(n_loc, cfg.f_gw_max / max(n_loc, 1))
    p_tx = cfg.p_max

    cumf, cumg = _cum(w.flops), _cum(w.mem)
    tot_f, tot_g = cumf[-1], cumg[-1]
    e_dev = (w.k_iters * w.d_tilde[devs] * cfg.v_dev / cfg.phi_dev
             * cumf[l] * net.f_dev[devs] ** 2)
    e_tra_gw = float(np.sum(w.k_iters * w.d_tilde[devs] * cfg.v_gw / cfg.phi_gw
                            * (tot_f - cumf[l]) * f_gw ** 2))
    e_up = net.uplink_energy(m, j, p_tx, w.gamma, st)
    mem_dev_ok = (cumg[l] <= cfg.g_dev_max).all()
    mem_gw_ok = float(np.sum(tot_g - cumg[l])) <= cfg.g_gw_max
    ok = (mem_dev_ok and mem_gw_ok and (e_dev <= st.e_dev[devs]).all()
          and (e_tra_gw + e_up) <= st.e_gw[m])

    t_train = float(np.max(_train_times(w, devs, l, net.f_dev[devs],
                                        cfg.phi_dev, cfg.phi_gw, f_gw)))
    lam = (t_train + net.uplink_time(m, j, p_tx, w.gamma, st)
           + net.downlink_time(m, j, w.gamma, st))
    return GatewaySolution(bool(ok), lam, l, f_gw, p_tx, e_dev,
                           e_tra_gw + e_up)


def _decision_for(ctx: RoundContext, chosen: np.ndarray) -> RoundDecision:
    """Build a RoundDecision for baseline scheduler given chosen gateways."""
    net = ctx.net
    m_gw, j_ch = net.cfg.n_gateways, net.cfg.n_channels
    eye = np.zeros((m_gw, j_ch))
    lam = np.full((m_gw, j_ch), np.inf)
    sols: Dict = {}
    for j, m in enumerate(chosen[:j_ch]):
        sol = _fixed_resource_solution(ctx, int(m), j)
        sols[(int(m), j)] = sol
        lam[int(m), j] = sol.delay
        eye[int(m), j] = 1.0
    selected = eye.sum(axis=1) > 0
    tau = float(np.where(eye > 0, lam, -np.inf).max())
    new_q = update_queues(ctx.queues, selected, ctx.gamma_rates)
    return RoundDecision(eye, selected, lam, sols, tau, new_q)


def round_robin_pick(t: int, m_gw: int, j_ch: int) -> np.ndarray:
    """Round Robin [26]: consecutive groups of J gateways."""
    start = (t * j_ch) % m_gw
    return (start + np.arange(j_ch)) % m_gw


def random_picks(seed: int, rounds: int, m_gw: int, j_ch: int) -> np.ndarray:
    """Random Scheduling [26]: uniform J gateways per round, drawn from a
    generator seeded with the run seed (one ``choice`` per round)."""
    rng = np.random.default_rng(seed)
    return np.stack([rng.choice(m_gw, size=j_ch, replace=False)
                     for _ in range(rounds)])


def delay_driven_pick(ctx: RoundContext) -> np.ndarray:
    """Select the J gateways with the smallest fixed-resource delay, each
    on its best channel."""
    m, j = ctx.net.cfg.n_gateways, ctx.net.cfg.n_channels
    delays = np.array([
        min(_fixed_resource_solution(ctx, mm, jj).delay for jj in range(j))
        for mm in range(m)])
    return np.argsort(delays)[:j]

