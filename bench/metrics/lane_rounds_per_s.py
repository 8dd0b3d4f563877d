"""Policy-grid lane-rounds (policies x seeds x V x rounds) completed per
second over the whole window (host clock)."""


def read(ctx):
    if ctx["unit"] != "lane-rounds":
        return None
    return ctx["res"]["units"] / ctx["res"]["window_s"]
