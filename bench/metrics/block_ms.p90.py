"""90th percentile, over every call of the window, of one fused block's
wall time until the host holds its records (host clock)."""
import numpy as np


def read(ctx):
    if ctx["unit"] != "rounds" or not ctx["res"]["latencies_s"]:
        return None
    return 1e3 * float(np.percentile(ctx["res"]["latencies_s"], 90))
