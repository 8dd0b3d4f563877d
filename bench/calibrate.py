"""Readings behind the limits of ``bench/limits/<cell>.json``: the numbers
of the comparison on many seeds of sound runs (the lower readings), of the
control, and of the planted faults (the upper readings). The benchmark's
own runs never run this.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --fault-seeds 4,5,6 --control-seeds 7,8,9 --out cal.json

Each seed builds the program's ``Simulation`` and runs the set-up calls the
comparison checks, exactly as ``bench/run.py`` does, without the window.

* The control is the next lower precision than the configuration states.
  The control plane runs in float32 (the program's decide programs with
  x64 left off, ``--control-seeds``). The data plane's own bfloat16 path
  does not compile on a TPU, so for every sound seed of a fused cell the
  reference, computed in bfloat16, is put in the program's place
  (``reference_in_place``) and compared with the float32 reference.
* Faults, fused cells: ``half_batch`` trains the reference (and makes its
  statistics) on the first half of each batch in the program's place;
  ``--fault-seeds`` run the program with the same fault in its train
  scan's gather alone (``half_batch_gather``); an answer altered where it
  is produced is read by flipping one gateway's pick in the program's
  records, and by counting each eval on the weights its block started
  from (``stale_eval``). A state left unchanged reads 1 by the update
  gaps' measure and needs no run. Grid cells: each lane's last delay
  altered, and half of the seeds' lanes left out (their outputs zeroed).
"""
from __future__ import annotations

import argparse
import contextlib
import copy
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


@contextlib.contextmanager
def x64_off(jax):
    """Leave x64 off inside the program's ``with jax.enable_x64(True)``
    blocks: its decide programs then compute in float32."""
    saved = jax.enable_x64
    jax.enable_x64 = lambda *a, **k: contextlib.nullcontext()
    try:
        yield
    finally:
        jax.enable_x64 = saved


@contextlib.contextmanager
def half_batch_gather():
    """The program's traced train scan gathers only the first half of each
    device's batch (rounded up) and takes its mean over those rows: the
    same compiled program, fed halved batch lengths."""
    import numpy as np
    from repro.fl import cohort
    orig = cohort.train_scan_traced

    def half(model, params, losses0, x_all, y_all, pool_lens, batch_lens,
             *a, **k):
        return orig(model, params, losses0, x_all, y_all, pool_lens,
                    (np.asarray(batch_lens) + 1) // 2, *a, **k)

    cohort.train_scan_traced = half
    try:
        yield
    finally:
        cohort.train_scan_traced = orig


def reference_in_place(config: dict, traffic: dict, seed: int, kept: dict,
                       dtype) -> dict:
    """``kept`` with the data plane's outputs made by the reference in
    ``dtype`` for the program's trained gateways: the weights after each
    block, each round's gateway losses and the evals' accuracy."""
    import jax
    import numpy as np
    from bench import compare
    from bench.reference import vgg_split_fl as ref
    sc = config["scenario"]
    dep = compare.reference_deployment(config)
    kinds, params = ref.init_vgg11(jax.random.PRNGKey(seed),
                                   sc["width_mult"], sc["classes"])
    local_train = ref.make_local_train(kinds, lr=sc["lr"],
                                       k_iters=sc["k_iters"], dtype=dtype)
    hits = ref.make_hits(kinds, dtype)
    rpc = int(traffic["rounds_per_call"])
    kept = copy.deepcopy(kept)

    def host(tree):
        return jax.tree.map(lambda a: np.asarray(a, np.float32),
                            jax.device_get(tree))

    snaps = [host(params)]
    for t, r in enumerate(kept["records"]):
        params, gw = ref.fl_round(local_train, dep, seed, params, t,
                                  r["trained"])
        r["losses"] = np.array(r["losses"], np.float64)
        for m, v in gw.items():
            r["losses"][m] = v
        if r["accuracy"] is not None:
            r["accuracy"] = float(hits(params, dep.x_test, dep.y_test)) \
                / len(dep.y_test)
        if (t + 1) % rpc == 0:
            snaps.append(host(params))
    kept["params"] = snaps
    return kept


def altered_fused(kept: dict) -> dict:
    """The kept records with one pick flipped: the gateway with the
    longest queue in the last checked round is marked unselected (or
    selected, when it was not)."""
    import numpy as np
    kept = copy.deepcopy(kept)
    r = kept["records"][-1]
    m = int(np.argmax(r["queues"]))
    r["selected"] = r["selected"].copy()
    r["selected"][m] = ~r["selected"][m]
    return kept


def altered_grid(kept: dict, half: bool) -> dict:
    import numpy as np
    kept = copy.deepcopy(kept)
    if half:       # the second half of the seeds' lanes never computed
        s = kept["taus"].shape[1]
        for k in ("taus", "selected", "queues"):
            kept[k][:, s // 2 + s % 2:] = 0
    else:          # every lane's last delay altered by 1%
        kept["taus"][..., -1] *= 1.01
    return kept


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from bench import compare, drive, run as bench_run
    import jax
    bench_run.use_cache(jax)
    _, cell, config, traffic = bench_run.cell_spec(args.workload)
    bench_run.check_device(jax, int(cell["chips"]))
    fused = traffic["call"] == "fused_rounds"
    out = {"workload": args.workload, "sound": {}, "control": {},
           "faults": {}, "device": jax.devices()[0].device_kind}

    def seeds(arg):
        return [int(s) for s in arg.split(",") if s]

    def save():
        Path(args.out).write_text(json.dumps(out, indent=1, default=float))

    for seed in seeds(args.seeds):
        t0 = time.perf_counter()
        d = drive.make(config, traffic, seed)
        d.setup()
        d.free()
        gc.collect()
        nums = d.numbers()
        faults = {}
        if fused:
            faults["half_batch"] = d.numbers(half_batch=True,
                                             parts=("stats", "train"))
            faults["answer_altered"] = compare.fused_numbers(
                config, traffic, seed, altered_fused(d.kept),
                parts=("decide",))
            faults["stale_eval"] = d.numbers(stale_eval=True,
                                             parts=("train",))
            out["control"].setdefault(seed, {})["bf16_reference"] = \
                compare.fused_numbers(
                    config, traffic, seed,
                    reference_in_place(config, traffic, seed, d.kept,
                                       jax.numpy.bfloat16),
                    parts=("train",))
        else:
            for name, half in (("answer_altered", False),
                               ("half_lanes", True)):
                kept = dict(altered_grid(d.kept, half), repeat_mismatch=0)
                faults[name] = compare.grid_numbers(config, traffic, seed,
                                                    kept)
        out["sound"][seed] = nums
        out["faults"][seed] = faults
        print(f"seed {seed}: {time.perf_counter() - t0:.1f}s {nums}",
              flush=True)
        save()
        del d
        gc.collect()

    for seed in seeds(args.fault_seeds) if fused else []:
        t0 = time.perf_counter()
        with half_batch_gather():
            d = drive.make(config, traffic, seed)
            d.setup()
        d.free()
        gc.collect()
        nums = d.numbers(parts=("train",))
        out["faults"].setdefault(seed, {})["half_batch_gather"] = nums
        print(f"fault {seed}: {time.perf_counter() - t0:.1f}s {nums}",
              flush=True)
        save()
        del d
        gc.collect()

    for seed in seeds(args.control_seeds):
        t0 = time.perf_counter()
        with x64_off(jax):
            d = drive.make(config, traffic, seed)
            try:
                d.setup()
            except Exception as e:                          # noqa: BLE001
                out["control"].setdefault(seed, {})["f32_decide"] = {
                    "crashed": repr(e)[:500]}
                save()
                continue
        d.free()
        gc.collect()
        nums = d.numbers(parts=("stats", "decide")) if fused else d.numbers()
        out["control"].setdefault(seed, {})["f32_decide"] = nums
        print(f"control {seed}: {time.perf_counter() - t0:.1f}s {nums}",
              flush=True)
        save()
        del d
        gc.collect()
    save()
    return 0


if __name__ == "__main__":
    sys.exit(main())
