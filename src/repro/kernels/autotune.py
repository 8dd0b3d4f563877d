"""Block-shape autotuner + persistent kernel-selection table.

The hand-written ``tile_plan`` heuristic (fixed 128^3 blocks clamped to the
problem) leaves roofline performance on the table: the best block shape for
a Pallas kernel depends on the problem shape, the dtype (bf16 halves HBM
traffic and doubles the useful VMEM tile budget) and the backend. This
module sweeps candidate block shapes per (op, shape, dtype, backend), times
each candidate through the *existing* jit/interpret call paths, and caches
the winners in a TensorRT-LLM-style selection table:

* **Persistent table** — one JSON file per op under ``artifacts/autotune/``
  (override with ``REPRO_AUTOTUNE_DIR``), keyed
  ``op|shape|dtype|backend``. Entries carry their own (shape, dtype,
  backend, blocks, us) so the key is re-derivable — CI validates the
  committed tables round-trip (load -> schema -> deterministic re-key).
* **In-process LRU** — resolved plans (including fallbacks) are memoized,
  so the hot path costs one dict hit per traced shape.
* **Exact-match -> clamped-heuristic fallback** — a lookup miss returns
  the op's default blocks (the historical 128-aligned heuristic, clamped
  by ``tile_plan`` at the call site). Cold keys never trigger a sweep and
  therefore never block a training round; sweeps only run when explicitly
  requested (``benchmarks/kernel_bench.py --autotune`` or the
  ``sweep_*`` functions here).

``blocks_for`` is the single source of block defaults for every op layer
(``fused_linear/ops.py``, ``flash_attention/ops.py``, ``ssd_scan/ops.py``)
— the old per-module ``_BLOCKS`` constants are gone, so routing
(``tile_plan``) and kernel block choices can never drift.
"""
from __future__ import annotations

import collections
import functools
import itertools
import json
import os
import pathlib
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

TABLE_VERSION = 1

# op -> (default blocks, block-tuple arity, block field names).
# fused_linear blocks are (block_m, block_k, block_n) — TilePlan field
# order; flash_attention (block_q, block_k); ssd_scan (chunk, block_h).
OPS: Dict[str, Tuple[Tuple[int, ...], Tuple[str, ...]]] = {
    "fused_linear": ((128, 128, 128), ("block_m", "block_k", "block_n")),
    "flash_attention": ((128, 128), ("block_q", "block_k")),
    "ssd_scan": ((128, 8), ("chunk", "block_h")),
}

_POW2 = (32, 64, 128, 256, 512)
_VMEM_F32_BUDGET = 3 << 20          # ~12 MB of f32 words across resident tiles
_LRU_MAX = 1024


def table_dir() -> pathlib.Path:
    """Directory holding the per-op selection tables (JSON)."""
    env = os.environ.get("REPRO_AUTOTUNE_DIR", "")
    if env:
        return pathlib.Path(env)
    return pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "autotune"


def backend_id(interpret: bool = False) -> str:
    """Selection-table backend key: the jax backend, ``-interpret`` when the
    kernels run under the Pallas interpreter (their timings differ wildly
    from compiled TPU timings, so they must never share entries)."""
    import jax
    return jax.default_backend() + ("-interpret" if interpret else "")


def make_key(op: str, shape: Sequence[int], dtype: str, backend: str) -> str:
    """``op|shape|dtype|backend`` — the deterministic table key."""
    return f"{op}|{'x'.join(str(int(s)) for s in shape)}|{dtype}|{backend}"


# ---------------------------------------------------------------------------
# table load / store
# ---------------------------------------------------------------------------

_TABLES: Dict[str, Dict[str, dict]] = {}          # op -> entries (in-process)
_LRU: "collections.OrderedDict[str, Tuple[int, ...]]" = collections.OrderedDict()


def _table_path(op: str) -> pathlib.Path:
    return table_dir() / f"{op}.json"


def _entries(op: str) -> Dict[str, dict]:
    """Lazily-loaded entries for ``op``; a missing or corrupt table is an
    empty one (the heuristic fallback must never be blocked by disk state)."""
    if op not in _TABLES:
        try:
            payload = json.loads(_table_path(op).read_text())
            entries = payload["entries"]
            assert isinstance(entries, dict)
        except (OSError, ValueError, KeyError, AssertionError):
            entries = {}
        _TABLES[op] = entries
    return _TABLES[op]


def clear_cache() -> None:
    """Drop the in-process table + LRU caches (tests; table regeneration)."""
    _TABLES.clear()
    _LRU.clear()


def _valid_blocks(op: str, blocks) -> Optional[Tuple[int, ...]]:
    default, _ = OPS[op]
    if (isinstance(blocks, (list, tuple)) and len(blocks) == len(default)
            and all(isinstance(b, int) and b > 0 for b in blocks)):
        return tuple(blocks)
    return None


def _exact(op: str, key: str) -> Optional[Tuple[int, ...]]:
    entry = _entries(op).get(key)
    return _valid_blocks(op, entry.get("blocks")) if entry else None


def exact_blocks(op: str, shape: Sequence[int], dtype: str, *,
                 interpret: bool = False,
                 backend: Optional[str] = None) -> Optional[Tuple[int, ...]]:
    """The blocks of an exact table entry for one call site, or None on a
    miss (a missing or corrupt table, or a malformed entry, is a miss):
    for an op layer that has its own rule to fall back on."""
    return _exact(op, make_key(op, shape, dtype,
                               backend or backend_id(interpret)))


def blocks_for(op: str, shape: Sequence[int], dtype: str, *,
               interpret: bool = False,
               backend: Optional[str] = None) -> Tuple[int, ...]:
    """Resolve block sizes for one kernel call site.

    Resolution order: in-process LRU -> exact table match -> the op's
    default blocks (the clamped-128 heuristic). Never sweeps, never
    raises on missing/corrupt tables — a cold key costs one dict miss.
    The caller still clamps/validates through ``tile_plan`` (or the
    kernel's own divisibility asserts), so a stale table entry can only
    cost performance, never correctness.
    """
    default, _ = OPS[op]
    key = make_key(op, shape, dtype, backend or backend_id(interpret))
    if key in _LRU:
        _LRU.move_to_end(key)
        return _LRU[key]
    blocks = _exact(op, key) or default
    _LRU[key] = blocks
    if len(_LRU) > _LRU_MAX:
        _LRU.popitem(last=False)
    return blocks


def record(op: str, shape: Sequence[int], dtype: str, backend: str,
           blocks: Sequence[int], us: float, baseline_us: float,
           *, save: bool = True) -> dict:
    """Store a sweep winner in the table (and on disk when ``save``)."""
    key = make_key(op, shape, dtype, backend)
    entry = {
        "shape": [int(s) for s in shape],
        "dtype": dtype,
        "backend": backend,
        "blocks": [int(b) for b in blocks],
        "us": float(us),
        "baseline_us": float(baseline_us),
        "speedup_vs_default": float(baseline_us / us) if us > 0 else 1.0,
    }
    _entries(op)[key] = entry
    _LRU.pop(key, None)
    if save:
        save_table(op)
    return entry


def save_table(op: str) -> pathlib.Path:
    """Write ``op``'s entries to its JSON table (sorted keys: stable diffs)."""
    path = _table_path(op)
    path.parent.mkdir(parents=True, exist_ok=True)
    payload = {
        "version": TABLE_VERSION,
        "op": op,
        "entries": {k: _entries(op)[k] for k in sorted(_entries(op))},
    }
    path.write_text(json.dumps(payload, indent=2) + "\n")
    return path


def validate_table(op: str) -> int:
    """Strict round-trip check of ``op``'s on-disk table, for CI.

    Loads the JSON, validates the schema, and re-derives every key from
    the entry's own (shape, dtype, backend) fields — a renamed/edited key
    or a blocks tuple of the wrong arity fails loudly here (unlike the
    forgiving runtime ``blocks_for`` path). Returns the entry count; a
    missing table is 0 entries.
    """
    path = _table_path(op)
    if not path.exists():
        return 0
    payload = json.loads(path.read_text())
    if payload.get("version") != TABLE_VERSION or payload.get("op") != op:
        raise ValueError(f"{path}: bad version/op header: "
                         f"{payload.get('version')!r}/{payload.get('op')!r}")
    entries = payload["entries"]
    for key, e in entries.items():
        rekey = make_key(op, e["shape"], e["dtype"], e["backend"])
        if rekey != key:
            raise ValueError(f"{path}: key {key!r} does not round-trip "
                             f"(re-derived {rekey!r})")
        if _valid_blocks(op, e["blocks"]) is None:
            raise ValueError(f"{path}: entry {key!r} has bad blocks "
                             f"{e['blocks']!r}")
        if not (float(e["us"]) > 0 and float(e["baseline_us"]) > 0):
            raise ValueError(f"{path}: entry {key!r} has non-positive timing")
    return len(entries)


# ---------------------------------------------------------------------------
# candidate enumeration
# ---------------------------------------------------------------------------


def _dim_candidates(dim: int) -> List[int]:
    """Power-of-two divisors of ``dim`` (<= 512) plus ``dim`` itself: every
    value yields an exactly-aligned tiling after ``tile_plan`` clamping."""
    out = [c for c in _POW2 if c <= dim and dim % c == 0]
    if dim <= 512 and dim not in out:
        out.append(dim)
    return sorted(out)


def candidates(op: str, shape: Sequence[int],
               max_candidates: int = 24) -> List[Tuple[int, ...]]:
    """Aligned candidate block tuples for (op, shape), VMEM-bounded.

    fused_linear shape is (m, k, n); flash_attention (b, h, s, d);
    ssd_scan (b, s, n, p, ds). The list is capped at ``max_candidates``,
    preferring larger blocks (fewer grid steps, better MXU utilization),
    and the clamped default blocks are always included when aligned — so
    a sweep can never pick something worse than the heuristic on its own
    timing metric.
    """
    if op == "fused_linear":
        m, k, n = shape
        combos = [
            (bm, bk, bn)
            for bm, bk, bn in itertools.product(
                _dim_candidates(m), _dim_candidates(k), _dim_candidates(n))
            # fwd tiles (bm,bk)+(bk,bn)+(bm,bn) resident in VMEM at once
            if bm * bk + bk * bn + bm * bn <= _VMEM_F32_BUDGET
        ]
    elif op == "flash_attention":
        b, h, s, d = shape
        combos = [
            (bq, bk)
            for bq, bk in itertools.product(_dim_candidates(s), repeat=2)
            if (bq + 2 * bk) * d + bq * bk + 2 * bq * d <= _VMEM_F32_BUDGET
        ]
    elif op == "ssd_scan":
        b, s, n, p, ds = shape
        combos = [
            (chunk, bh)
            for chunk in _dim_candidates(s)
            for bh in (1, 2, 4, 8, 16)
            if n % min(bh, n) == 0
            and chunk * chunk * bh + bh * ds * p <= _VMEM_F32_BUDGET
        ]
        combos = sorted(set((c, min(bh, n)) for c, bh in combos))
    else:
        raise KeyError(f"unknown op {op!r}; known: {sorted(OPS)}")
    combos = sorted(set(combos),
                    key=lambda c: (-_volume(c), c))[:max_candidates]
    default = tuple(min(b, s_) for b, s_ in _clamp_pairs(op, shape))
    aligned = all(s_ % min(b, s_) == 0
                  for b, s_ in _clamp_pairs(op, shape))
    if aligned and default not in combos:
        combos.append(default)
    return sorted(combos)


def _volume(blocks: Tuple[int, ...]) -> int:
    v = 1
    for b in blocks:
        v *= b
    return v


def _clamp_pairs(op: str, shape: Sequence[int]) -> Iterable[Tuple[int, int]]:
    """(default block, clamping dim) pairs — which shape axis each block
    dimension clamps against."""
    default, _ = OPS[op]
    if op == "fused_linear":
        m, k, n = shape
        dims = (m, k, n)
    elif op == "flash_attention":
        dims = (shape[2], shape[2])          # both blocks tile the seq axis
    else:                                    # ssd_scan
        dims = (shape[1], shape[2])          # chunk | seq, block_h | heads
    return zip(default, dims)


# ---------------------------------------------------------------------------
# sweeps (explicit only — the lookup path never calls these)
# ---------------------------------------------------------------------------


def _time_call(fn, *args, iters: int = 3, repeats: int = 2) -> float:
    """us/call: warm up (compile), then best mean over ``repeats`` runs."""
    import jax
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best * 1e6


def _default_interpret() -> bool:
    import jax
    return jax.default_backend() != "tpu"


def sweep_fused_linear(m: int, k: int, n: int, dtype: str = "float32",
                       *, activation: str = "relu",
                       interpret: Optional[bool] = None, iters: int = 3,
                       save: bool = True, seed: int = 0) -> dict:
    """Sweep (block_m, block_k, block_n) for one fused_linear GEMM shape and
    record the winner. Times the *forward* kernel; the backward kernels tile
    the same (m, k, n) triple, so one winner routes the whole custom VJP
    (see ``fused_linear/ops.py``)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.fused_linear.kernel import fused_linear, tile_plan
    from repro.kernels.fused_linear.ref import fused_linear_ref

    interpret = _default_interpret() if interpret is None else interpret
    jdt = jnp.dtype(dtype)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (m, k), jnp.float32).astype(jdt)
    w = (jax.random.normal(ks[1], (k, n), jnp.float32) / max(k, 1) ** 0.5
         ).astype(jdt)
    b = jnp.zeros((n,), jdt)

    def timed(blocks) -> float:
        bm, bk, bn = blocks
        fn = jax.jit(functools.partial(
            fused_linear, activation=activation, block_m=bm, block_k=bk,
            block_n=bn, interpret=interpret))
        return _time_call(fn, x, w, b, iters=iters)

    default, _ = OPS["fused_linear"]
    base_plan = tile_plan(m, k, n, block_m=default[0], block_n=default[2],
                          block_k=default[1])
    if base_plan.aligned:
        baseline = timed((base_plan.block_m, base_plan.block_k,
                          base_plan.block_n))
    else:    # default plan would route to ref — that's the time to beat
        fn = jax.jit(lambda a, b_, c: fused_linear_ref(a, b_, c, activation))
        baseline = _time_call(fn, x, w, b, iters=iters)

    cands = candidates("fused_linear", (m, k, n))
    if not cands:
        return None          # no aligned tiling exists; ref path only
    best_blocks, best_us = None, float("inf")
    for cand in cands:
        us = timed(cand)
        if us < best_us:
            best_blocks, best_us = cand, us
    return record("fused_linear", (m, k, n), str(jdt),
                  backend_id(interpret), best_blocks, best_us, baseline,
                  save=save)


def sweep_flash_attention(b: int, h: int, s: int, d: int,
                          dtype: str = "float32", *, causal: bool = True,
                          interpret: Optional[bool] = None, iters: int = 3,
                          save: bool = True, seed: int = 0) -> dict:
    """Sweep (block_q, block_k) for one (B, H, S, D) attention shape."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_attention.kernel import flash_attention

    interpret = _default_interpret() if interpret is None else interpret
    jdt = jnp.dtype(dtype)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q, k_, v = (jax.random.normal(kk, (b, h, s, d), jnp.float32).astype(jdt)
                for kk in ks)

    def timed(blocks) -> float:
        bq, bk = blocks
        fn = jax.jit(functools.partial(
            flash_attention, causal=causal, block_q=bq, block_k=bk,
            interpret=interpret))
        return _time_call(fn, q, k_, v, iters=iters)

    cands = candidates("flash_attention", (b, h, s, d))
    if not cands:
        return None
    default, _ = OPS["flash_attention"]
    base = tuple(min(c, s) for c in default)
    if all(s % c == 0 for c in base):
        baseline = timed(base)
    else:    # default blocks can't tile s — the jnp oracle is the time to beat
        from repro.kernels.flash_attention.ref import attention_ref
        fn = jax.jit(functools.partial(attention_ref, causal=causal))
        baseline = _time_call(fn, q, k_, v, iters=iters)
    best_blocks, best_us = None, float("inf")
    for cand in cands:
        us = timed(cand)
        if us < best_us:
            best_blocks, best_us = cand, us
    return record("flash_attention", (b, h, s, d), str(jdt),
                  backend_id(interpret), best_blocks, best_us, baseline,
                  save=save)


def sweep_ssd_scan(b: int, s: int, n: int, p: int, ds: int,
                   dtype: str = "float32", *,
                   interpret: Optional[bool] = None, iters: int = 3,
                   save: bool = True, seed: int = 0) -> dict:
    """Sweep (chunk, block_h) for one (B, S, n, p, ds) SSD-scan shape."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.ssd_scan.kernel import ssd_scan

    interpret = _default_interpret() if interpret is None else interpret
    jdt = jnp.dtype(dtype)
    k = jax.random.PRNGKey(seed)
    xh = jax.random.normal(k, (b, s, n, p), jnp.float32).astype(jdt)
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(k, 1),
                                           (b, s, n))) * 0.5
    a_log = jax.random.normal(jax.random.fold_in(k, 2), (n,)) * 0.3
    b_ssm = (jax.random.normal(jax.random.fold_in(k, 3), (b, s, ds)) * 0.5
             ).astype(jdt)
    c_ssm = (jax.random.normal(jax.random.fold_in(k, 4), (b, s, ds)) * 0.5
             ).astype(jdt)

    def timed(blocks) -> float:
        chunk, bh = blocks
        fn = jax.jit(functools.partial(ssd_scan, chunk=chunk, block_h=bh,
                                       interpret=interpret))
        return _time_call(fn, xh, dt, a_log, b_ssm, c_ssm, iters=iters)

    cands = candidates("ssd_scan", (b, s, n, p, ds))
    if not cands:
        return None
    default, _ = OPS["ssd_scan"]
    base = (min(default[0], s), min(default[1], n))
    if s % base[0] == 0 and n % base[1] == 0:
        baseline = timed(base)
    else:
        from repro.kernels.ssd_scan.ref import ssd_ref
        fn = jax.jit(ssd_ref)
        baseline = _time_call(fn, xh, dt, a_log, b_ssm, c_ssm, iters=iters)
    best_blocks, best_us = None, float("inf")
    for cand in cands:
        us = timed(cand)
        if us < best_us:
            best_blocks, best_us = cand, us
    return record("ssd_scan", (b, s, n, p, ds), str(jdt),
                  backend_id(interpret), best_blocks, best_us, baseline,
                  save=save)


def _main() -> None:
    import argparse
    ap = argparse.ArgumentParser(
        description="Validate the committed kernel-selection tables "
                    "(load -> schema -> deterministic re-key).")
    ap.add_argument("--check", action="store_true",
                    help="strict round-trip validation of every op table")
    args = ap.parse_args()
    if args.check:
        for op in OPS:
            n = validate_table(op)
            print(f"{op}: {n} entries OK ({_table_path(op)})")


if __name__ == "__main__":
    _main()
