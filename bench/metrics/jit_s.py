"""Seconds the program spent tracing, lowering and compiling, by the
program's own counters (``compile.<span>.*`` of ``repro.obs``): set-up's,
since the window compiles nothing (``compiles_in_window``)."""
from bench import program


def read(ctx):
    return program.jit_seconds()
