"""Per-round device time of the in-scan test-set evaluation (scope ``eval``:
the ``lax.cond``-gated forward): the operations in that named scope of whole
train-program executions in the trace, on the busiest chip, over the rounds
one call does (``bench/program.py``)."""
from bench import program


def read(ctx):
    return program.scope_ms_per_round(ctx, "eval")
