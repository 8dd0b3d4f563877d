"""Reduction of a profiler trace to the benchmark's device numbers.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
:func:`load` reads it with ``jax.profiler.ProfileData`` into a
:class:`Trace`: per device, the operations that ran (the ``XLA Ops`` line)
and the compiled programs they belong to (the ``XLA Modules`` line), and on
the host the benchmark's own ``jax.profiler.TraceAnnotation`` spans, whose
names start with ``bench.``. Everything after loading works on that plain
structure, so it is checked on synthetic traces in the tests.

Times are nanoseconds on the profiler's clock, which it keeps common to the
host and device planes.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple

Span = Tuple[str, int, int]          # (name, start_ns, end_ns)

SPAN_PREFIX = "bench."
# operations read per device: the x64 control plane runs over a million
# tiny operations a second, and reading them costs about 25 us each; past
# this many the trace is read as if cut there (see ``window``)
MAX_OPS = 1_000_000


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Span]]       # device id -> device operations
    modules: Dict[int, List[Span]]   # device id -> program executions
    host: List[Span]                 # the benchmark's own host spans
    # earliest time from which a device's trace holds no more operations:
    # its buffer overflowed ("Trace Buffers Dropped"), or MAX_OPS were read
    dropped_from: Optional[int] = None


def find_xplane(log_dir) -> Path:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _device_id(plane_name: str) -> Optional[int]:
    m = re.match(r"^/device:(TPU|GPU):(\d+)", plane_name)
    return int(m.group(2)) if m else None


def load(path) -> Trace:
    """Read one ``.xplane.pb`` into a :class:`Trace`."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    ops: Dict[int, List[Span]] = {}
    modules: Dict[int, List[Span]] = {}
    host: List[Span] = []
    dropped: List[int] = []
    for plane in data.planes:
        dev = _device_id(plane.name)
        for line in plane.lines:
            if dev is not None and line.name == "XLA TraceMe":
                dropped += [int(ev.start_ns) for ev in line.events
                            if "Dropped" in ev.name]
            elif dev is not None and line.name in ("XLA Ops",
                                                   "XLA Modules"):
                dest = ops if line.name == "XLA Ops" else modules
                spans = dest.setdefault(dev, [])
                for ev in line.events:
                    if line.name == "XLA Ops" and len(spans) >= MAX_OPS:
                        dropped.append(int(ev.start_ns))
                        break
                    spans.append((ev.name, int(ev.start_ns),
                                  int(ev.start_ns + ev.duration_ns)))
            elif dev is None and plane.name.startswith("/host"):
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        host.append((ev.name, int(ev.start_ns),
                                     int(ev.start_ns + ev.duration_ns)))
    return Trace(ops, modules, host, min(dropped, default=None))


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def union(intervals: Sequence[Tuple[int, int]], lo: int, hi: int
          ) -> List[Tuple[int, int]]:
    """Disjoint sorted union of the intervals, clipped to [lo, hi]."""
    out: List[List[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Tuple[int, int]], lo: int, hi: int
         ) -> List[Tuple[int, int]]:
    """The complement of a disjoint sorted union within [lo, hi]."""
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def clipped_ns(spans: Sequence[Span], lo: int, hi: int) -> int:
    """Summed length of the spans inside [lo, hi] (overlaps counted as
    often as they occur)."""
    return sum(max(0, min(e, hi) - max(s, lo)) for _, s, e in spans)


# ---------------------------------------------------------------------------
# what the metrics read
# ---------------------------------------------------------------------------


def window(tr: Trace, name: str = "bench.window") -> Tuple[int, int]:
    """[start, end] of the host span ``name`` (the measured window), cut
    short where the trace stops holding operations (``dropped_from``):
    past that point idle time would be invented."""
    spans = [(s, e) for n, s, e in tr.host if n == name]
    if not spans:
        raise ValueError(f"no host span {name!r} in the trace")
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    if tr.dropped_from is not None and tr.dropped_from < hi:
        # end with the last call that the trace still holds whole, so
        # that time and the work counted in it belong to the same calls
        ends = [e for n, s, e in tr.host
                if n == "bench.call" and lo <= s and e <= tr.dropped_from]
        hi = max(ends, default=max(lo, tr.dropped_from))
    return lo, hi


def busy_ns(tr: Trace, dev: int, lo: int, hi: int) -> int:
    """Time in [lo, hi] in which some operation ran on device ``dev``."""
    return sum(e - s for s, e in
               union([(s, e) for _, s, e in tr.ops.get(dev, [])], lo, hi))


def idle_share(tr: Trace, lo: int, hi: int) -> Dict[int, float]:
    """1 - busy / window, per device."""
    return {d: 1.0 - busy_ns(tr, d, lo, hi) / (hi - lo) for d in tr.ops}


def whole_executions(tr: Trace, dev: int, pattern: str, lo: int, hi: int
                     ) -> Tuple[int, int]:
    """(summed device time, count) of the executions of the programs whose
    name matches ``pattern`` that lie whole inside [lo, hi]: the work of
    whole calls, wherever the window or a cut trace ends."""
    rx = re.compile(pattern)
    whole = [e - s for n, s, e in tr.modules.get(dev, [])
             if rx.search(n) and lo <= s and e <= hi]
    return sum(whole), len(whole)


def ops_ns(tr: Trace, dev: int, pred: Callable[[str], bool], lo: int,
           hi: int) -> int:
    """Device time in [lo, hi] of the operations whose name passes
    ``pred``."""
    return clipped_ns([sp for sp in tr.ops.get(dev, []) if pred(sp[0])],
                      lo, hi)


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12 fusion``: the
    HLO instruction's name and kind, where the event carries its whole
    text."""
    head, _, rest = event_name.partition(" = ")
    head = head.strip().lstrip("%")
    kind = re.search(r"\s([a-z][\w-]*)\(", " " + rest) if rest else None
    return f"{head} {kind.group(1)}" if kind else head


def is_container(event_name: str) -> bool:
    """Loops and branches: their events span the operations inside them."""
    text = event_name.split(" = ", 1)[-1]
    return bool(re.search(r"(?<![\w-])(while|conditional|call)\(", text))


def is_kernel(event_name: str) -> bool:
    """A Pallas kernel: a custom call in the HLO text."""
    return " custom-call(" in event_name


def top_ops(tr: Trace, dev: int, lo: int, hi: int, k: int = 10
            ) -> List[List]:
    """The ``k`` operations (loops and branches left out, since their
    time is that of what runs inside them) with the most device time in
    [lo, hi], as [name, seconds]; names keep the HLO instruction's
    kind, so ``fusion.12`` and ``custom-call`` kernels read apart."""
    tot: Dict[str, int] = {}
    for n, s, e in tr.ops.get(dev, []):
        d = max(0, min(e, hi) - max(s, lo))
        if d and not is_container(n):
            key = op_name(n)
            tot[key] = tot.get(key, 0) + d
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, ns / 1e9] for n, ns in best]


def idle_gaps(tr: Trace, dev: int, lo: int, hi: int, k: int = 10
              ) -> List[List]:
    """The ``k`` longest stretches of [lo, hi] with nothing running on
    ``dev``, each named after the innermost host span around its middle
    (``"host"`` where none is), as [name, seconds]."""
    busy = union([(s, e) for _, s, e in tr.ops.get(dev, [])], lo, hi)
    out = []
    for s, e in gaps(busy, lo, hi):
        mid = (s + e) // 2
        around = [(n, hs, he) for n, hs, he in tr.host
                  if hs <= mid <= he and n != "bench.window"]
        name = min(around, key=lambda sp: sp[2] - sp[1])[0] if around \
            else "host"
        out.append([name, (e - s) / 1e9])
    out.sort(key=lambda g: -g[1])
    return out[:k]
