"""Per-round device time of the two-tier FedAvg (scope ``fedavg``: the weighted
average, the gateway-loss reduction and the no-trainer guard): the
operations in that named scope of whole train-program executions in the
trace, on the busiest chip, over the rounds one call does
(``bench/program.py``)."""
from bench import program


def read(ctx):
    return program.scope_ms_per_round(ctx, "fedavg")
