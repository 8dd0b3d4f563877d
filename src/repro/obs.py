"""The program's tracing: host spans, compile attribution and counters.

``span(name, **attrs)`` wraps ``jax.profiler.TraceAnnotation``: when the
profiler records, the span lands in its trace on the same clock as the
device operations; when it does not, it costs what an inactive
annotation costs (about a microsecond). Either way the span adds its
duration to :data:`totals` and keeps a per-thread stack of open spans,
and an outermost span is kept in :data:`calls` with the summed time of
every span inside it (``parts``), so a reader can split a call into its
steps without a trace. Names are ``repro.<layer>[.<step>]``; attributes
carry what the spans of one call share (``block=``, ``rounds=``, ...).

One ``jax.monitoring`` listener attributes JAX's tracing, lowering and
backend-compile durations to the innermost open span, as
``compile.<span>.{n, trace_s, lower_s, backend_s}`` in :data:`counters`
(``compile.unspanned.*`` outside every span); ``n`` counts the programs
traced there, so a retrace is named by the step it happened in. It runs
only when JAX compiles. ``count(name)`` adds to the same registry, e.g.
the trace-time counters ``trace.<module>.<program>`` that the compiled
bodies bump each time JAX traces them.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Callable, Deque, Dict, List

import jax

# span name -> [count, seconds]
totals: Dict[str, List[float]] = {}
# counter name -> value (retrace counters, compile attribution)
counters: Dict[str, float] = collections.defaultdict(float)
# the latest outermost spans, oldest first
calls: Deque["span"] = collections.deque(maxlen=4096)
# program name -> gives the optimized HLO text of the latest program traced
# under that name (``call_keeping``), whose metadata names each operation's
# scope: a device trace names operations, not scopes
programs: Dict[str, Callable[[], str]] = {}

_local = threading.local()

_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
}


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class span:
    """Context manager over one host span; ``with span(...) as sp`` gives
    the span, whose ``seconds`` holds its duration once it has closed."""

    __slots__ = ("name", "attrs", "seconds", "parts", "seq", "_t0", "_tm")

    def __init__(self, name: str, **attrs):
        self.name, self.attrs = name, attrs
        self.seconds = 0.0
        self.parts: Dict[str, float] = {}
        self.seq = 0

    def __enter__(self) -> "span":
        _stack().append(self)
        self._tm = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self._tm.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._tm.__exit__(*exc)
        stack = _stack()
        stack.pop()
        tot = totals.setdefault(self.name, [0, 0.0])
        tot[0] += 1
        tot[1] += self.seconds
        self.seq = tot[0]
        if stack:
            parts = stack[-1].parts
            parts[self.name] = parts.get(self.name, 0.0) + self.seconds
            for k, v in self.parts.items():
                parts[k] = parts.get(k, 0.0) + v
        else:
            calls.append(self)


def current() -> str:
    """Name of the innermost open span on this thread ('' outside all)."""
    stack = _stack()
    return stack[-1].name if stack else ""


def count(name: str, n: float = 1) -> None:
    counters[name] += n


def _abstract(leaf):
    if isinstance(leaf, jax.Array):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=leaf.sharding,
                                    weak_type=leaf.weak_type)
    return leaf


def call_keeping(name: str, counter: str, fn, *args, **kwargs):
    """``fn(*args, **kwargs)`` for a jitted ``fn`` whose body bumps the
    trace-time ``counter``; where this call traced it anew, keep in
    :data:`programs` under ``name`` how to compile it again, from the
    arguments' shapes alone (no device buffer is held)."""
    before = counters[counter]
    out = fn(*args, **kwargs)
    if counters[counter] != before:
        specs = jax.tree.map(_abstract, args)
        programs[name] = lambda: _compiled_text(fn, specs, kwargs)
    return out


def _compiled_text(fn, specs, kwargs) -> str:
    """The optimized HLO text of ``fn`` at ``specs``, compiled apart from
    every executable already cached: the caches key a program without its
    metadata, so a cached executable may carry the op_names of a build
    without these scopes. Instruction names do not depend on metadata, so
    they are those of the executable that ran."""
    flag = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, flag)
    jax.clear_caches()
    jax.config.update(flag, True)
    try:
        return fn.lower(*specs, **kwargs).compile().as_text()
    finally:
        jax.config.update(flag, was)


def _on_duration(event: str, duration: float, **_) -> None:
    kind = _COMPILE_EVENTS.get(event)
    if kind is None:
        return
    key = f"compile.{current() or 'unspanned'}"
    # a jitted function traced while another is traced or lowered reports
    # its own event first; fold it into the enclosing one, so that the
    # seconds are wall time and n counts the programs traced at the top
    done = getattr(_local, "traces", None)
    if done is None:
        done = _local.traces = collections.deque(maxlen=4096)
    start = time.perf_counter() - duration
    while done and done[-1][0] >= start:
        _, inner, inner_key = done.pop()
        counters[f"{inner_key}.trace_s"] -= inner
        counters[f"{inner_key}.n"] -= 1
    if kind == "trace_s":
        done.append((start, duration, key))
        counters[f"{key}.n"] += 1
    counters[f"{key}.{kind}"] += duration


jax.monitoring.register_event_duration_secs_listener(_on_duration)
