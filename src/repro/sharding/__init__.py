"""Sharding substrate for the repro system.

Two families of helpers live here:

* **Model-parallel parameter sharding** — logical-axis rules mapped to
  ``jax.sharding.PartitionSpec`` trees (re-exported from
  ``repro.models.params``): ``DEFAULT_RULES``, ``partition_specs``,
  ``rules_for_mesh``.
* **Cohort-axis data parallelism** — the 1-D ``"cohort"`` mesh the sharded
  FL engine (``repro.fl.shard``) maps device *slots* over while replicating
  model parameters: ``COHORT_AXIS``, ``cohort_mesh``, and the two
  canonical specs ``SLOT_SPEC`` (leading slot axis sharded) /
  ``REPLICATED``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from repro.models.params import (DEFAULT_RULES, partition_specs,
                                 rules_for_mesh)

# The mesh axis the sharded cohort engine maps device slots over.
COHORT_AXIS = "cohort"

# Canonical specs for the cohort mesh: per-slot arrays shard their leading
# axis; model parameters / global reductions are replicated. Whole-run
# fused loops (repro.fl.fused_sim) stack rounds in front of the slot axis,
# so their per-slot arrays shard axis 1 instead (STACKED_SLOT_SPEC).
SLOT_SPEC = PartitionSpec(COHORT_AXIS)
STACKED_SLOT_SPEC = PartitionSpec(None, COHORT_AXIS)
REPLICATED = PartitionSpec()


@functools.lru_cache(maxsize=None)
def cohort_mesh(mesh_shape: Optional[Tuple[int, ...]] = None) -> Mesh:
    """Build the 1-D ``"cohort"`` mesh for the sharded FL engine.

    ``mesh_shape`` is the (optionally multi-dim, flattened) device count to
    request; ``None`` uses every addressable device (one device on a
    single-device host, where the sharded engine runs as a plain fused
    program with mathematically identical results). Asking for more devices
    than the process has raises ``ValueError``: a mesh that silently shrank
    would run a four-chip configuration on one chip. Use
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to exercise a
    real multi-device CPU mesh in tests.
    """
    devices = jax.devices()
    want = len(devices) if mesh_shape is None else int(np.prod(mesh_shape))
    if not 1 <= want <= len(devices):
        raise ValueError(
            f"cohort mesh {mesh_shape} needs {want} devices; this process "
            f"has {len(devices)} ({devices[0].platform})")
    return Mesh(np.asarray(devices[:want]), (COHORT_AXIS,))


__all__ = ["DEFAULT_RULES", "partition_specs", "rules_for_mesh",
           "COHORT_AXIS", "SLOT_SPEC", "STACKED_SLOT_SPEC", "REPLICATED",
           "cohort_mesh"]
