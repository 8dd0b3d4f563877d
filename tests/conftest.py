# Smoke tests and benches must see the host's real device count (1 CPU);
# only repro.launch.dryrun (run as a subprocess) forces 512 host devices.
# No XLA_FLAGS are set here on purpose.
import contextlib

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _seed_numpy():
    np.random.seed(0)


class _CompileCounter:
    """Live view over one or more compile-count sources.

    A source is either the name of a ``repro.obs`` counter — the trace-time
    counters the compiled bodies bump (``trace.cohort.round``,
    ``trace.shard.train_scan``, ``trace.ddsra.decide``, ...) — or a jitted
    callable, read through ``_cache_size()``. ``count`` is the number of
    traces since the counter was entered, summed over all sources.
    """

    def __init__(self, sources):
        self._sources = tuple(sources)
        self._start = self._read()

    def _read(self) -> int:
        from repro import obs
        total = 0
        for s in self._sources:
            if isinstance(s, str):
                total += int(obs.counters.get(s, 0))
            else:
                total += s._cache_size()
        return total

    @property
    def count(self) -> int:
        return self._read() - self._start


@pytest.fixture
def compile_count():
    """Factory for compile/retrace counters (shared across the suite).

    Usage::

        with compile_count("trace.cohort.round") as c:
            ... run rounds ...
        assert c.count <= 1          # one trace, zero retraces

    Pass several sources to count them jointly; pass a jitted function to
    count via its ``_cache_size()`` instead of a counter name.
    ``c.count`` also reads *inside* the block (it is a live delta).
    """
    @contextlib.contextmanager
    def factory(*sources):
        yield _CompileCounter(sources)
    return factory
