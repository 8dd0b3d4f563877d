"""Model FLOP utilization of the whole step: the operations training
needs (real trained samples x K local steps x forward+backward operations
per sample, plus the in-scan evaluations' forwards; padding and
recomputation left out, ``bench/flops.py``) over the traced window's
length, over the chips' bf16 peak (``bench/peaks.json``)."""
from bench import flops


def read(ctx):
    if ctx["trace"] is None or ctx["unit"] != "rounds":
        return None
    c = ctx["counts"]
    per = flops.vgg11_train_flops(c["width_mult"], c["classes"])
    fwd = flops.vgg11_forward_flops(c["width_mult"], c["classes"])
    ops = (c["real_samples"] * c["k_iters"] * per
           + c["eval_rows"] * fwd) * ctx["frac"]
    if ops <= 0:
        return None
    window_s = (ctx["hi"] - ctx["lo"]) / 1e9
    peak = flops.peaks(ctx["device_kind"])["flops_per_s"]
    return 100.0 * ops / window_s / (ctx["chips"] * peak)
