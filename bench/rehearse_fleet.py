"""Compile rehearsal for the four-chip fleet configuration ``vgg11-fleet48``
(24 gateways, 48 devices, 12 channels; VGG-11 at full width), without a
chip: the program's stats and fused train programs are compiled for a
described TPU v5e and their ``memory_analysis()`` printed, for one chip
(cohort engine) and per chip of a 2x2 host (sharded engine, ``"cohort"``
mesh of 4).

    JAX_PLATFORMS=cpu python3 bench/rehearse_fleet.py [--devices 48]

Nothing runs; the numbers are the compiler's, not measurements. The
script reaches into the program's jitted programs directly, since building
a ``Simulation`` would run the stats pass on the host.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def _compiled(lowered) -> dict:
    """memory_analysis() of the compiled program, or the compiler's
    refusal (a program that does not fit the chip's memory)."""
    try:
        return _mem(lowered.compile())
    except Exception as e:                                  # noqa: BLE001
        msg = str(e)
        first = [ln for ln in msg.splitlines() if ln.strip()][:3]
        return {"refused": " ".join(first)[:400]}


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    keys = ("temp_size_in_bytes", "argument_size_in_bytes",
            "output_size_in_bytes", "alias_size_in_bytes")
    return {k: int(getattr(m, k, 0)) for k in keys}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--devices", type=int, default=48)
    ap.add_argument("--gateways", type=int, default=24)
    ap.add_argument("--channels", type=int, default=12)
    ap.add_argument("--width", type=int, default=100,
                    help="slot width: alpha x max_dataset = 0.05 x 2000")
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--only", choices=("one", "four", "both"),
                    default="both")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
        SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    from repro.fl import cohort as cohort_lib
    from repro.fl import shard as shard_lib
    from repro.models import split_model as sm

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    model = sm.VGGSplitModel(width_mult=1.0, classes=10)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n, w, t = args.devices, args.width, args.rounds
    slots = args.channels * (n // args.gateways)
    pool = 2000
    out = {"devices": n, "gateways": args.gateways,
           "channels": args.channels, "slots": slots, "width": w}

    def sds(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def tree(sharding):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype, sharding),
                            params)

    def train_args(sh_rep, sh_slot, n_slots):
        return (tree(sh_rep), sds((args.gateways,), jnp.float32, sh_rep),
                sds((n, pool, 32, 32, 3), jnp.float32, sh_rep),
                sds((n, pool), jnp.int32, sh_rep),
                sds((n,), jnp.int32, sh_rep), sds((n,), jnp.int32, sh_rep),
                sds((2,), jnp.uint32, sh_rep),
                sds((t,), jnp.int32, sh_rep),
                (sds((t, n_slots), jnp.int32, sh_slot),))

    if args.only in ("one", "both"):
        one = SingleDeviceSharding(topo.devices[0])
        out["one_chip_stats"] = _compiled(cohort_lib._cohort_stats.lower(
            model, tree(one), sds((n, w, 32, 32, 3), jnp.float32, one),
            sds((n, w), jnp.int32, one), sds((n, w), jnp.float32, one),
            sds((n,), jnp.float32, one), sds((), jnp.float32, one),
            sigma_samples=8))
        a = train_args(one, one, slots)
        out["one_chip_train"] = _compiled(cohort_lib.train_scan_traced.lower(
            model, *a[:8], a[8], (sds((t, slots), jnp.int32, one),),
            (sds((t, slots), jnp.float32, one),),
            (sds((t, slots, args.gateways), jnp.float32, one),),
            sds((t, args.gateways), jnp.bool_, one),
            sds((), jnp.float32, one), sds((t,), jnp.bool_, one),
            sds((1000, 32, 32, 3), jnp.float32, one),
            sds((1000,), jnp.int32, one), k_iters=5,
            tier_widths=(w,)))
        print(json.dumps(out), flush=True)

    if args.only in ("four", "both"):
        mesh = Mesh(np.array(topo.devices[:4]), (shard_lib.COHORT_AXIS,))
        rep = NamedSharding(mesh, PartitionSpec())
        tile = NamedSharding(mesh, PartitionSpec(shard_lib.COHORT_AXIS))
        stk = NamedSharding(mesh, PartitionSpec(None, shard_lib.COHORT_AXIS))
        fn = shard_lib._stats_program(mesh, model, 8)
        out["four_chip_stats_per_chip"] = _compiled(fn.lower(
            tree(rep), sds((n, w, 32, 32, 3), jnp.float32, tile),
            sds((n, w), jnp.int32, tile), sds((n, w), jnp.float32, tile),
            sds((n,), jnp.float32, tile), sds((), jnp.float32, rep)))
        fn = shard_lib._train_scan_program_traced(mesh, model, 5, 1, "f32",
                                                  (w,))
        a = train_args(rep, stk, slots)
        lowered = fn.lower(
            *a[:8], a[8], (sds((t, slots), jnp.float32, stk),),
            (sds((t, slots, args.gateways), jnp.float32, stk),),
            sds((t, args.gateways), jnp.bool_, rep),
            sds((), jnp.float32, rep), sds((t,), jnp.bool_, rep),
            sds((1000, 32, 32, 3), jnp.float32, rep),
            sds((1000,), jnp.int32, rep))
        out["four_chip_train_per_chip"] = _compiled(lowered)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
