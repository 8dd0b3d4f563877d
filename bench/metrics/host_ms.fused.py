"""Host time per simulated round of the window's fused calls: each
``repro.fused`` span (channel draws, decide dispatch, slot packing, train
dispatch, records) less its two waits for the device, over the rounds it
ran (the program's own spans, ``bench/program.py``)."""
from bench import program


def read(ctx):
    if ctx["unit"] != "rounds":
        return None
    s = program.host_seconds_per_unit(ctx, "repro.fused", ("rounds",))
    return None if s is None else 1e3 * s
