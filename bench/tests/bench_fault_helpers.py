"""Shared pieces of the fault tests: the harness's load generator and
comparison run on the CPU at a tiny width, judged against the cell's own limits
(``bench/limits``), with the program wrapped where a test breaks it."""
import contextlib
import copy
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))

from bench import drive, run as bench_run  # noqa: E402
from repro.fl import Simulation  # noqa: E402

SEED = 2 ** 31 + 11


def cell(name, **traffic_kw):
    _, _, config, traffic = bench_run.cell_spec(name)
    config = copy.deepcopy(config)
    config["scenario"]["width_mult"] = 0.0625
    traffic = dict(copy.deepcopy(traffic), **traffic_kw)
    return config, traffic, bench_run.cell_limits(name)


def correct_after(config, traffic, limits, **kw) -> bool:
    d = drive.make(config, traffic, SEED, **kw)
    d.setup()
    res = drive.window(d, 0.01)
    d.free()
    _, ok = bench_run.judge(d.numbers(), limits)
    return ok and res["failed"] == 0


@contextlib.contextmanager
def wrapped(name, wrap):
    orig = getattr(Simulation, name)
    setattr(Simulation, name, wrap(orig))
    try:
        yield
    finally:
        setattr(Simulation, name, orig)


def unchanged_state(orig):
    def f(self, *a, **k):
        before = self.params
        out = orig(self, *a, **k)
        self.params = before
        return out
    return f


def altered_pick(orig):
    def f(self, *a, **k):
        recs = orig(self, *a, **k)
        r = recs[-1]
        m = int(np.argmax(r.queues))
        r.selected = r.selected.copy()
        r.selected[m] = ~r.selected[m]
        return recs
    return f




def altered_accuracy(orig):
    def f(self, *a, **k):
        recs = orig(self, *a, **k)
        done = [r for r in recs if r.accuracy is not None]
        if done:
            done[-1].accuracy = float(done[-1].accuracy) + 0.05
        return recs
    return f
