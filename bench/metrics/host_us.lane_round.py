"""Host time per lane-round of the window's sweep calls: each
``repro.sweep`` span (channel draws, policy picks, dispatch with its x64
transfers) less its wait for the device, over its lanes x rounds (the
program's own spans, ``bench/program.py``)."""
from bench import program


def read(ctx):
    if ctx["unit"] != "lane-rounds":
        return None
    s = program.host_seconds_per_unit(ctx, "repro.sweep",
                                      ("lanes", "rounds"))
    return None if s is None else 1e6 * s
