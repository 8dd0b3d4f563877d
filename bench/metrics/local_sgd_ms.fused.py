"""Per-round device time of the slots' K local split-SGD steps (scope
``local_sgd``: the per-slot broadcast, forward, backward and update): the
operations in that named scope of whole train-program executions in the
trace, on the busiest chip, over the rounds one call does
(``bench/program.py``)."""
from bench import program


def read(ctx):
    return program.scope_ms_per_round(ctx, "local_sgd")
