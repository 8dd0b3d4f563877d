"""Pod-axis pipeline split — the paper's DNN partition mapped to TPU pods.

The paper's device/gateway tier split becomes a two-stage GPipe pipeline
over the multi-pod mesh's ``pod`` axis: pod 0 (≙ device tier) owns the
bottom layers, pod 1 (≙ gateway tier) owns the top layers; boundary
activations flow pod0->pod1 over ICI during forward and boundary errors
flow pod1->pod0 during backward — exactly the split-learning exchange of
Sec. II-B3, with ``repro.core.partition.best_partition`` choosing the cut
from per-layer TPU costs instead of WiFi rates.

Implementation: ``shard_map`` over the pod axis; each pod runs its stage on
a microbatch stream; ``jax.lax.ppermute`` moves boundary tensors between
stages. Stage weights are stacked with a leading pod dim so each pod reads
only its own slice (true pipeline parallelism, not replication).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.partition import Tier, best_partition


@dataclasses.dataclass(frozen=True)
class PipelineCut:
    """Chosen partition for a layered model on a 2-pod mesh."""
    cut: int              # layers [0, cut) on pod 0, [cut, L) on pod 1
    n_layers: int

    @property
    def stage_layers(self) -> Tuple[int, int]:
        return self.cut, self.n_layers - self.cut


def choose_cut(costs: np.ndarray, mem: np.ndarray, hbm_per_pod: float,
               boundary_bytes: Optional[np.ndarray] = None,
               ici_bw: float = 50e9, throughput: float = 197e12 * 256) -> PipelineCut:
    """Run the paper's bisection over TPU per-layer costs (sub-problem 21)."""
    tier = Tier(throughput=throughput, mem_capacity=hbm_per_pod)
    cut = best_partition(costs, mem, tier, tier,
                         boundary_bytes=boundary_bytes, link_bw=ici_bw,
                         objective="bottleneck")
    if cut is None:
        raise ValueError("no feasible pipeline partition")
    return PipelineCut(cut, len(costs))


def _stage_apply(layer_fn: Callable, stage_params, x, n_layers: int):
    """Run ``n_layers`` stacked layers sequentially on this stage."""
    def body(c, lp):
        return layer_fn(lp, c), None
    y, _ = jax.lax.scan(body, x, stage_params)
    return y


def gpipe_forward(layer_fn: Callable, params_stacked, x,
                  mesh, n_micro: int, layers_per_stage: int):
    """Two-stage GPipe forward over the 'pod' mesh axis.

    params_stacked: pytree with leading dims (2, layers_per_stage, ...)
                    sharded P('pod', ...); x: (B, ...) batch-partitioned
                    microbatch stream (B = n_micro * mb).
    Returns y: (B, ...) logits-side activations produced by stage 1.

    Schedule: n_micro + 1 ticks; at each tick stage 0 consumes microbatch i
    and ppermutes its boundary activation to stage 1, which processes the
    previous tick's activation (classic 1F1B fill/drain for 2 stages).
    """
    pod_axis = "pod"

    def per_pod(stage_params, xs):
        # stage_params: (1, layers_per_stage, ...) local slice; drop pod dim
        stage_params = jax.tree.map(lambda t: t[0], stage_params)
        pod_id = jax.lax.axis_index(pod_axis)
        mb = jnp.reshape(xs, (n_micro, xs.shape[0] // n_micro) + xs.shape[1:])

        def tick(carry, i):
            pending = carry                   # activation received last tick
            my_in = jnp.where(pod_id == 0,
                              mb[jnp.minimum(i, n_micro - 1)], pending)
            out = _stage_apply(layer_fn, stage_params, my_in, layers_per_stage)
            # stage0 -> stage1 handoff
            recv = jax.lax.ppermute(out, pod_axis, [(0, 1)])
            # only stage 1 emits finished microbatches; psum makes the
            # result identical on both pods (out_specs is replicated)
            y_done = jax.lax.psum(
                jnp.where(pod_id == 1, out, jnp.zeros_like(out)), pod_axis)
            return recv, y_done

        _, ys = jax.lax.scan(tick, jnp.zeros_like(mb[0]), jnp.arange(n_micro + 1))
        # stage 1 produced valid outputs on ticks 1..n_micro
        ys = ys[1:]
        return jnp.reshape(ys, xs.shape)

    spec_params = jax.tree.map(lambda _: P(pod_axis), params_stacked)
    return jax.shard_map(
        per_pod, mesh=mesh,
        in_specs=(spec_params, P(None)),
        out_specs=P(None),
        check_vma=False,
    )(params_stacked, x)


# ---------------------------------------------------------------------------
# demo layer: the fused-linear unit the split-FL experiment uses
# ---------------------------------------------------------------------------


def mlp_layer_fn(lp, x):
    return jax.nn.relu(x @ lp["w"] + lp["b"])


def build_demo(mesh, n_layers: int = 8, width: int = 512, batch: int = 32,
               n_micro: int = 4, rng=None):
    """A runnable 2-stage pipeline demo (also used by tests)."""
    assert n_layers % 2 == 0
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(rng)
    w = jax.random.normal(k1, (2, n_layers // 2, width, width)) * (width ** -0.5)
    b = jnp.zeros((2, n_layers // 2, width))
    x = jax.random.normal(k2, (batch, width))
    params = {"w": w, "b": b}
    y = gpipe_forward(mlp_layer_fn, params, x, mesh, n_micro, n_layers // 2)
    return params, x, y


def reference_forward(params, x):
    """Unpipelined oracle for the demo."""
    w = params["w"].reshape(-1, *params["w"].shape[2:])
    b = params["b"].reshape(-1, *params["b"].shape[2:])
    for i in range(w.shape[0]):
        x = jax.nn.relu(x @ w[i] + b[i])
    return x
