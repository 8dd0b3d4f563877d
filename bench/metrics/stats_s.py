"""Seconds of the program's data-statistics pass (the program's own host
timer, ``Simulation.stats_seconds``)."""
import math


def read(ctx):
    v = ctx.get("stats_s")
    return None if v is None or math.isnan(v) else v
