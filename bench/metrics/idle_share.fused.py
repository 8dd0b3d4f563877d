"""Share of the traced window in which nothing ran on the busiest chip,
in a fused cell."""
from bench import trace


def read(ctx):
    tr = ctx["trace"]
    if tr is None or ctx["unit"] != "rounds":
        return None
    return 100.0 * min(trace.idle_share(tr, ctx["lo"], ctx["hi"])[d]
                       for d in ctx["dev_ids"])
