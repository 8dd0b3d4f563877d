"""Roofline share of the fused_linear Pallas kernels (the fc layers'
forward, input-gradient and weight-gradient GEMMs of every slot's K local
steps): the least time their operations and bytes need on this chip
(``bench/flops.py``, ``bench/peaks.json``) over their device time in the
trace (custom-call operations, busiest chip). Every round runs them, also
one that trains nobody, on every slot at the slot width."""
from bench import flops, trace


def read(ctx):
    tr = ctx["trace"]
    c = ctx["counts"]
    if tr is None or ctx["unit"] != "rounds" or not c.get("trained_rounds") \
            or not c.get("slots"):
        return None
    kernel_ns = max(trace.ops_ns(tr, d, trace.is_kernel, ctx["lo"],
                                 ctx["hi"]) for d in ctx["dev_ids"])
    if not kernel_ns:
        return None
    slots = c["slots"]
    width = round(c["padded_samples"] / c["trained_rounds"] / slots)
    peak = flops.peaks(ctx["device_kind"])
    per_round = sum(flops.least_time(o, b, peak)[0] for _, o, b in
                    flops.fc_kernel_calls(width, c["width_mult"],
                                          c["classes"], copies=slots))
    least_s = per_round * c["k_iters"] * ctx["units"]
    return 100.0 * least_s / (kernel_ns / 1e9)
