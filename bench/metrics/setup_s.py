"""Set-up time: the program's Simulation (data, weights, stats pass), the
warm-up of the cell's shapes and the calls the comparison checks, on the
host clock."""


def read(ctx):
    return ctx["setup_s"]
