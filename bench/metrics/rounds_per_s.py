"""Simulated FL rounds completed per second over the whole window (host
clock)."""


def read(ctx):
    if ctx["unit"] != "rounds":
        return None
    return ctx["res"]["units"] / ctx["res"]["window_s"]
