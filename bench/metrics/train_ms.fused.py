"""Device time of the fused train program per simulated round, from the
trace: the mean whole execution (one per call) over the units one call
does, on the busiest chip."""
from bench import trace


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["units_per_call"]:
        return None
    per = []
    for d in ctx["dev_ids"]:
        ns, n = trace.whole_executions(tr, d, r"train_scan", ctx["lo"],
                                       ctx["hi"])
        if n:
            per.append(ns / n)
    return max(per) / 1e+06 / ctx["units_per_call"] if per else None
