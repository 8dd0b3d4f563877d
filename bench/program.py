"""Readings of the program's own instrumentation (``repro.obs``): its host
spans, the named scopes of its train program, and what it compiled.

The program keeps, per outermost span, the summed time of every span
inside it (``obs.calls``), so a call's host work is its length less its
waits for the device, with no trace needed. The device trace names
operations by their HLO instruction, so the scope of each comes from the
metadata of the train program's optimized HLO text, which the program
keeps a way to compile again (``obs.programs``).

A checkout whose program has no ``repro.obs`` (or no such span, scope or
program) reads nothing here: every reading is then ``None``.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Tuple

from bench import trace

# the train program's named scopes, in the order of a round
SCOPES = ("gather", "local_sgd", "fedavg", "eval")
TRAIN = r"train_scan"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OPCODE = re.compile(r"(?<![\w.\-%])[a-z][\w\-]*\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")


def obs():
    """The program's ``repro.obs``, or ``None`` where it has none."""
    try:
        from repro import obs as mod
    except ImportError:
        return None
    return mod


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------


def setup_calls(ctx) -> int:
    """Calls the set-up makes before the window: a fused mix's
    ``check_calls``, a sweep's one."""
    return int(ctx["traffic"].get("check_calls", 1))


def host_seconds_per_unit(ctx, root: str, unit_attrs: Tuple[str, ...]
                          ) -> Optional[float]:
    """Host time per unit of the window's calls of span ``root``: each
    call's length less its ``*.wait`` spans (the host blocked on the
    device), summed over the calls after set-up's, over the units they did
    (the product of the root's ``unit_attrs``)."""
    mod = obs()
    if mod is None:
        return None
    calls = [c for c in getattr(mod, "calls", ())
             if c.name == root and c.seq > setup_calls(ctx)]
    if not calls:
        return None
    host = units = 0.0
    for c in calls:
        waits = sum(v for k, v in c.parts.items() if k.endswith(".wait"))
        host += c.seconds - waits
        n = 1
        for a in unit_attrs:
            n *= int(c.attrs[a])
        units += n
    return host / units


def jit_seconds() -> Optional[float]:
    """Seconds the program spent tracing, lowering and compiling
    (``compile.<span>.{trace_s, lower_s, backend_s}``, every span and
    outside them), leaving out what the benchmark's own readings compiled
    (under ``bench.*`` spans)."""
    mod = obs()
    if mod is None or not hasattr(mod, "counters"):
        return None
    keys = [k for k in mod.counters if k.startswith("compile.")
            and not k.startswith("compile.bench.")
            and k.endswith(("trace_s", "lower_s", "backend_s"))]
    return sum(mod.counters[k] for k in keys) if keys else None


# ---------------------------------------------------------------------------
# named scopes of the train program
# ---------------------------------------------------------------------------


def _operands(line: str, at: int) -> List[str]:
    """Names of the instructions in the operand list that opens at
    ``line[at]`` (the parenthesis after the opcode)."""
    depth = 0
    for i in range(at, len(line)):
        depth += {"(": 1, ")": -1}.get(line[i], 0)
        if depth == 0:
            return _REF.findall(line[at:i])
    return _REF.findall(line[at:])


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> its named scope for every instruction of an
    optimized HLO module's text: the innermost of :data:`SCOPES` in its
    ``op_name`` metadata. An instruction the compiler added without one
    (a copy, a kernel reversed for a gradient, a conversion hoisted out
    of the loop) takes the scope of the nearest instruction that reads its
    result, else of the nearest it reads; ``""`` where none has one."""
    own: Dict[str, str] = {}
    users: Dict[str, List[str]] = {}
    reads: Dict[str, List[str]] = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        meta = _OP_NAME.search(line)
        found = [p for p in (meta.group(1).split("/") if meta else [])
                 if p in SCOPES]
        own[name] = found[-1] if found else ""
        op = _OPCODE.search(line, m.end())
        reads[name] = _operands(line, op.end() - 1) if op else []
        for r in reads[name]:
            users.setdefault(r, []).append(name)
    out = dict(own)
    for name, scope in own.items():
        if not scope:
            out[name] = _nearest(name, users, own) \
                or _nearest(name, reads, own)
    return out


def _nearest(name: str, edges: Dict[str, List[str]],
             own: Dict[str, str]) -> str:
    """The scope of the nearest instruction along ``edges`` that has one
    of its own (breadth first), or ``""``."""
    seen, frontier = {name}, [name]
    while frontier:
        nxt = []
        for n in frontier:
            for m in edges.get(n, ()):
                if m in seen:
                    continue
                if own.get(m):
                    return own[m]
                seen.add(m)
                nxt.append(m)
        frontier = nxt
    return ""


def scope_ns(tr: trace.Trace, dev: int, lo: int, hi: int,
             scopes: Dict[str, str]) -> Tuple[Dict[str, int], int]:
    """Device time of the operations of the whole ``train_scan``
    executions inside [lo, hi] on ``dev``, by named scope (``""``: none),
    loops and branches left out (their time is that of what runs in
    them); and the number of those executions."""
    execs = sorted((s, e) for n, s, e in tr.modules.get(dev, [])
                   if re.search(TRAIN, n) and lo <= s and e <= hi)
    starts = [s for s, _ in execs]
    by: Dict[str, int] = {}
    for name, s, e in tr.ops.get(dev, []):
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or e > execs[i][1] or trace.is_container(name):
            continue
        key = scopes.get(trace.op_name(name).split(" ")[0], "")
        by[key] = by.get(key, 0) + (e - s)
    return by, len(execs)


def scope_ms_per_round(ctx, scope: str) -> Optional[float]:
    """Per-round device time of the operations in ``scope``, in whole
    ``train_scan`` executions on the busiest chip (the one with the most
    such time)."""
    if ctx["trace"] is None or ctx["unit"] != "rounds" \
            or not ctx["units_per_call"]:
        return None
    if "program_scopes" not in ctx:
        ctx["program_scopes"] = _busiest_scope_ns(ctx)
    got = ctx["program_scopes"]
    if got is None or scope not in got[2]:
        return None
    by, n_exec, _ = got
    return by.get(scope, 0) / n_exec / ctx["units_per_call"] / 1e6


def _busiest_scope_ns(ctx):
    """(ns by scope, executions, the scopes the program has) on the
    busiest chip, or ``None``."""
    mod = obs()
    get = getattr(mod, "programs", {}).get("train_scan") if mod else None
    if get is None:
        return None
    # compiled again (repro.obs); kept out of jit_s by its span
    with mod.span("bench.read.scopes"):
        scopes = hlo_scopes(get())
    best = None
    for d in ctx["dev_ids"]:
        by, n = scope_ns(ctx["trace"], d, ctx["lo"], ctx["hi"], scopes)
        if n and (best is None or sum(by.values()) > sum(best[0].values())):
            best = (by, n)
    return None if best is None else best + (set(scopes.values()),)
