"""Pallas kernel stack — the compute hot-spots of the split-training path.

Each subpackage is a (kernel.py, ops.py, ref.py) triple:

* ``kernel.py`` — the Pallas TPU kernels themselves (grid/BlockSpec level);
* ``ref.py`` — pure-jnp oracles with the same contraction structure (the
  numerics baseline for tests and the CPU/GPU fallback);
* ``ops.py`` — the differentiable public entry point that routes between
  them (shape-alignment predicate, ``jax.custom_vjp``, impl selection).

``fused_linear`` is the one the FL engines train through: forward
``act(x @ w + b)`` plus a dedicated backward subsystem — a transposed-
operand ``dz @ wᵀ`` kernel and an ``xᵀ @ dz`` kernel with the ``db``
column-reduction fused in, both applying the relu activation mask inline
from the saved output so ``dz``/``w.T``/``x.T`` are never materialized in
HBM (design notes: ``docs/architecture.md``, "The kernel stack"). One
shared ``kernel.tile_plan`` gates pallas-vs-ref routing for forward and
both backward contractions; ``kernel.skinny_plan`` tiles a slot's
skinny GEMMs from their shape. Set ``REPRO_FUSED_LINEAR_IMPL=interpret`` to
execute the kernel bodies on CPU (CI does, for tests/test_kernels.py).

``autotune`` is the cross-cutting module: a block-shape autotuner and a
persistent per-op selection table (``artifacts/autotune/*.json``, keyed
``op|shape|dtype|backend``) that every ops layer consults through
``autotune.blocks_for`` — exact autotuned match when one exists, the
clamped-128 heuristic otherwise; cold keys never sweep. ``fused_linear``
asks ``autotune.exact_blocks`` and falls back to its own rules. Regenerate with
``benchmarks/kernel_bench.py --autotune``; validate with
``python -m repro.kernels.autotune --check``. The kernels run f32 VMEM
accumulation for every operand dtype, which is what makes the bf16
mixed-precision data plane (``Scenario.dtype="bf16"``) safe.

Add new subpackages only for compute the paper itself optimizes with a
custom kernel.
"""
