"""Plain reference of split federated learning with VGG-11 (paper Sec. III,
VII-A), frozen with the benchmark.

Everything here is made again from the run seed, with nothing taken from
the program: the deployment (devices' dataset sizes and classes, the
synthetic non-IID CIFAR-shaped data), the VGG-11 weights, the per-device
data statistics behind the participation rates of Eq. (13), and each
round's local SGD and two-tier FedAvg. Every trained device runs K
full-batch SGD steps on its batch; the gateway average followed by the base
station's average is one d~-weighted average over the trained devices.

The network is written out layer by layer in ``jax.numpy``: no kernels, no
batching over devices in training, no scans. A batch is zero-padded to a
fixed width with a row mask, so one compiled step serves every device; the
masked rows add exact zeros to the loss and its gradient.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import control_plane as cp

VGG11_PLAN = [64, "M", 128, "M", 256, 256, "M", 512, 512, "M", 512, 512, "M"]


# ---------------------------------------------------------------------------
# deployment and data, from the seed
# ---------------------------------------------------------------------------


def _class_templates(rng: np.random.Generator, classes: int, size: int = 32):
    """Smooth random template per class (low-frequency Fourier pattern)."""
    t = []
    coords = np.linspace(0, 2 * np.pi, size)
    xx, yy = np.meshgrid(coords, coords)
    for _ in range(classes):
        img = np.zeros((size, size, 3))
        for c in range(3):
            for _ in range(4):
                fx, fy = rng.integers(1, 4, 2)
                ph = rng.uniform(0, 2 * np.pi, 2)
                img[:, :, c] += (rng.normal() * np.sin(fx * xx + ph[0])
                                 * np.cos(fy * yy + ph[1]))
        t.append(img / np.abs(img).max())
    return np.stack(t)


def _sample(rng, templates, cls: np.ndarray, noise: float = 0.35):
    base = templates[cls]
    jitter = rng.normal(0, noise, base.shape)
    bright = rng.uniform(0.7, 1.3, (len(cls), 1, 1, 1))
    return (base * bright + jitter).astype(np.float32)


def make_dataset(sizes: np.ndarray, q_classes: np.ndarray, chi: float,
                 classes: int, seed: int, test_size: int = 1000):
    """Per-device shards of ``q`` classes (share ``chi`` non-IID) and an IID
    test set: returns (x_dev, y_dev, x_test, y_test)."""
    rng = np.random.default_rng(seed)
    templates = _class_templates(rng, classes)
    x_dev, y_dev = [], []
    for n in range(len(sizes)):
        own = rng.choice(classes, size=min(int(q_classes[n]), classes),
                         replace=False)
        d = int(sizes[n])
        n_noniid = int(round(chi * d))
        y = np.concatenate([
            rng.choice(own, size=n_noniid),
            rng.integers(0, classes, size=d - n_noniid),
        ]).astype(np.int32)
        rng.shuffle(y)
        x_dev.append(_sample(rng, templates, y))
        y_dev.append(y)
    y_test = np.tile(np.arange(classes), test_size // classes).astype(np.int32)
    x_test = _sample(rng, templates, y_test)
    return x_dev, y_dev, x_test, y_test


@dataclasses.dataclass
class Deployment:
    """The fleet a seed makes: topology, data, batch sizes and the stats
    batch each device's statistics are estimated on."""
    seed: int
    net: cp.Network
    d_sizes: np.ndarray
    d_tilde: np.ndarray
    x_dev: List[np.ndarray]
    y_dev: List[np.ndarray]
    x_test: np.ndarray
    y_test: np.ndarray
    stats_idx: List[np.ndarray]       # per device: rows of the stats batch
    workload: cp.Workload
    net_rng_state0: dict              # channel stream after the topology


def deployment(seed: int, net_cfg: cp.NetworkConfig, *, width_mult: float,
               classes: int, alpha: float, max_dataset: int, chi: float,
               k_iters: int) -> Deployment:
    """Draw the deployment exactly as the paper's setup does: dataset sizes
    D_n ~ U(0, max_dataset], batches D~_n = alpha D_n, gateway 0's devices
    see every class and the others 1-3 classes; devices attach to gateways
    round-robin."""
    net = cp.Network(net_cfg, np.random.default_rng(seed))
    net_rng_state0 = net.rng.bit_generator.state
    rng = np.random.default_rng(seed + 1)
    n_dev = net_cfg.n_devices
    d_sizes = np.maximum(rng.uniform(0, max_dataset, n_dev).astype(int), 40)
    d_tilde = np.maximum((alpha * d_sizes).astype(int), 4)
    q = np.zeros(n_dev, dtype=int)
    for n in range(n_dev):
        q[n] = classes if net.assign[n] == 0 else int(rng.integers(1, 4))
    x_dev, y_dev, x_test, y_test = make_dataset(d_sizes, q, chi, classes,
                                                seed)
    # the statistics batch: one draw without replacement per device
    stats_idx = [rng.choice(len(y_dev[n]), size=min(int(d_tilde[n]),
                                                   len(y_dev[n])),
                            replace=False) for n in range(n_dev)]
    layers = cp.vgg11_layers(width_mult, classes=classes)
    workload = cp.Workload(cp.flops_vector(layers),
                           cp.mem_vector(layers, batch=int(d_tilde.max())),
                           cp.model_size_bytes(layers), k_iters,
                           d_tilde.astype(float))
    return Deployment(seed, net, d_sizes, d_tilde, x_dev, y_dev, x_test,
                      y_test, stats_idx, workload, net_rng_state0)


def batch_indices(seed: int, t: int, dev: int, pool_len: int, size: int,
                  l_max: int) -> np.ndarray:
    """Rows device ``dev`` trains on in round ``t``: a counter-based draw
    keyed by (seed + 2, t, dev) — ``l_max`` uniforms, rows past the shard
    excluded, the ``size`` smallest in ascending order (ties to the lower
    row)."""
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(seed + 2), t), dev)
    u = np.asarray(jax.random.uniform(key, (l_max,)))
    u = np.where(np.arange(l_max) < pool_len, u, np.inf)
    return np.argsort(u, kind="stable")[:size]


# ---------------------------------------------------------------------------
# VGG-11 with the paper's CIFAR-10 head
# ---------------------------------------------------------------------------


def init_vgg11(key, width_mult: float = 1.0, classes: int = 10,
               image: int = 32):
    """He-normal weights and zero biases, in layer order; one key split per
    weighted layer. Returns (kinds, params) with params a list of dicts."""
    kinds, params = [], []
    ci, hw = 3, image
    for item in VGG11_PLAN:
        if item == "M":
            kinds.append("pool")
            params.append({})
            hw //= 2
            continue
        co = max(1, int(item * width_mult))
        key, k = jax.random.split(key)
        kinds.append("conv")
        params.append({"w": jax.random.normal(k, (3, 3, ci, co))
                       * math.sqrt(2.0 / (ci * 9)),
                       "b": jnp.zeros((co,))})
        ci = co
    fc1 = max(16, int(4096 * width_mult))
    dims = [(ci * hw * hw, fc1), (fc1, fc1), (fc1, classes)]
    for i, (si, so) in enumerate(dims):
        key, k = jax.random.split(key)
        kinds.append("fc_last" if i == len(dims) - 1 else "fc")
        params.append({"w": jax.random.normal(k, (si, so))
                       * math.sqrt(2.0 / si),
                       "b": jnp.zeros((so,))})
    return tuple(kinds), params


def forward(kinds: Sequence[str], params, x):
    """Logits of a (B, 32, 32, 3) batch in float32, at the default matmul
    precision, which is what the configuration states (on a TPU one bf16
    pass with float32 accumulation, for the convolutions and the fc
    layers' kernels alike)."""
    for kind, p in zip(kinds, params):
        if kind == "conv":
            y = jax.lax.conv_general_dilated(
                x, p["w"], (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            x = jax.nn.relu(y + p["b"])
        elif kind == "pool":
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        else:
            y = x.reshape(x.shape[0], -1) @ p["w"] + p["b"]
            x = y if kind == "fc_last" else jax.nn.relu(y)
    return x


def masked_xent(logits, labels, mask):
    """Mean cross-entropy over the rows with mask 1."""
    logp = jax.nn.log_softmax(logits)
    ll = jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    return -jnp.sum(mask * ll) / jnp.maximum(jnp.sum(mask), 1.0)


def flat(tree):
    return jnp.concatenate([jnp.ravel(a) for a in jax.tree.leaves(tree)])


# ---------------------------------------------------------------------------
# the data statistics behind the participation rates (Sec. VII-A)
# ---------------------------------------------------------------------------


def _pad(x, y, width):
    b = len(y)
    xp = np.zeros((width,) + x.shape[1:], np.float32)
    yp = np.zeros((width,), np.int32)
    mp = np.zeros((width,), np.float32)
    xp[:b], yp[:b], mp[:b] = x, y, 1.0
    return xp, yp, mp


def participation(dep: Deployment, kinds, params0, *, lr: float,
                  k_iters: int, sigma_samples: int, n_channels: int,
                  half_batch: bool = False):
    """sigma_n (mean per-sample gradient deviation over the first
    ``sigma_samples`` rows of the stats batch), delta_n (distance of the
    batch gradient from the D_n-weighted global one) and L_n (secant along
    one SGD step, floored at 0.1), then Phi_m and Gamma_m. Devices are
    handled one at a time. Returns (gamma (M,), DataStats).

    ``half_batch`` plants a fault for the comparison's own readings: the
    batch gradients use the first half of each batch only."""
    width = int(dep.d_tilde.max())

    def loss(p, x, y, m):
        return masked_xent(forward(kinds, p, x), y, m)

    grad = jax.jit(lambda p, x, y, m: flat(jax.grad(loss)(p, x, y, m)))
    per_sample = jax.jit(jax.vmap(
        lambda p, x, y: flat(jax.grad(loss)(p, x[None], y[None],
                                            jnp.ones((1,), jnp.float32))),
        in_axes=(None, 0, 0)))

    @jax.jit
    def shifted(p, g):
        leaves, tdef = jax.tree.flatten(p)
        out, i = [], 0
        for a in leaves:
            out.append(a - lr * g[i:i + a.size].reshape(a.shape))
            i += a.size
        return jax.tree.unflatten(tdef, out)

    n_dev = len(dep.d_tilde)
    grads, sigma, lips = [], np.zeros(n_dev), np.zeros(n_dev)
    flat0 = flat(params0)
    for n in range(n_dev):
        idx = dep.stats_idx[n]
        x, y, m = _pad(dep.x_dev[n][idx], dep.y_dev[n][idx], width)
        if half_batch:
            m[(len(idx) + 1) // 2:] = 0.0
        g = grad(params0, x, y, m)
        s = min(sigma_samples, width)
        per = per_sample(params0, x[:s], y[:s])
        ms = jnp.asarray(m[:s])
        cnt = jnp.maximum(jnp.sum(ms), 1.0)
        mean_g = jnp.sum(per * ms[:, None], axis=0) / cnt
        dev = jnp.linalg.norm(per - mean_g[None], axis=1)
        sigma[n] = float(jnp.sum(dev * ms) / cnt)
        p1 = shifted(params0, g)
        g2 = grad(p1, x, y, m)
        dw = float(jnp.linalg.norm(flat(p1) - flat0))
        lips[n] = float(jnp.linalg.norm(g2 - g)) / max(dw, 1e-9)
        grads.append(g)
    mix = dep.d_sizes / dep.d_sizes.sum()
    global_g = sum(float(w) * g for w, g in zip(mix, grads))
    delta = np.array([float(jnp.linalg.norm(g - global_g)) for g in grads])
    stats = cp.DataStats(sigma, delta, np.maximum(lips, 0.1),
                         dep.d_tilde.astype(float))
    phi = cp.divergence_bound(stats, dep.net.assign, lr, k_iters)
    return cp.participation_rates(phi, n_channels), stats


# ---------------------------------------------------------------------------
# one FL round: K local SGD steps per trained device, then FedAvg
# ---------------------------------------------------------------------------


def make_local_train(kinds, *, lr: float, k_iters: int, dtype=jnp.float32):
    """Jitted K-step full-batch SGD on one padded batch; returns the final
    parameters and the loss of the last step (taken before its update).
    ``dtype`` is the precision of the weights, the batch and every
    operation: float32 as the configuration states, or a lower one for
    the control."""

    def loss(p, x, y, m):
        return masked_xent(forward(kinds, p, x), y, m)

    @jax.jit
    def local_train(p, x, y, m):
        p = jax.tree.map(lambda w: w.astype(dtype), p)
        x, m = x.astype(dtype), m.astype(dtype)
        last = jnp.zeros((), dtype)
        for _ in range(k_iters):
            last, g = jax.value_and_grad(loss)(p, x, y, m)
            p = jax.tree.map(lambda w, gw: w - lr * gw, p, g)
        return p, last

    return local_train


def make_hits(kinds, dtype=jnp.float32):
    """Jitted count of test rows whose arg-max logit is the label."""

    @jax.jit
    def hits(p, x, y):
        p = jax.tree.map(lambda w: w.astype(dtype), p)
        logits = forward(kinds, p, x.astype(dtype))
        return jnp.sum(jnp.argmax(logits, -1) == y)

    return hits


def fl_round(local_train, dep: Deployment, run_seed: int, params, t: int,
             trained_gateways: Sequence[int], *, half_batch: bool = False):
    """Round ``t`` of a run seeded ``run_seed``, for the given trained
    gateways: every device of each trains on its round-``t`` batch, then
    the d~-weighted average. Returns
    (new params, {gateway: mean last-step loss of its devices}).

    ``half_batch`` plants a fault for the comparison's own readings: each
    device trains on the first half of its batch only."""
    if not trained_gateways:
        return params, {}
    pools = [len(y) for y in dep.y_dev]
    l_max = max(pools)
    width = int(dep.d_tilde.max())
    finals, weights, gw_losses = [], [], {}
    for m in trained_gateways:
        losses = []
        for n in dep.net.devices_of(m):
            b = int(min(dep.d_tilde[n], pools[n]))
            idx = batch_indices(run_seed, t, int(n), pools[n], b, l_max)
            x, y, mask = _pad(dep.x_dev[n][idx], dep.y_dev[n][idx], width)
            if half_batch:
                mask[(b + 1) // 2:] = 0.0
            p, last = local_train(params, x, y, mask)
            finals.append(p)
            weights.append(float(dep.d_tilde[n]))
            losses.append(float(last))
        gw_losses[int(m)] = float(np.mean(losses))
    w = np.asarray(weights) / np.sum(weights)
    new = jax.tree.map(lambda *ls: sum(float(wi) * a
                                       for wi, a in zip(w, ls)), *finals)
    return new, gw_losses
