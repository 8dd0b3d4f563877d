"""Per-round device time of the in-program batch gather (scope ``gather``:
every slot's rows drawn from the device-resident shard stacks): the
operations in that named scope of whole train-program executions in the
trace, on the busiest chip, over the rounds one call does
(``bench/program.py``)."""
from bench import program


def read(ctx):
    return program.scope_ms_per_round(ctx, "gather")
