"""The ``jax.experimental.pallas.tpu`` symbols the kernels use.

Kernels import these names from here, never from ``pallas.tpu`` directly,
so a rename in JAX is absorbed in one file.

This module is the *only* place in the library allowed to import
``jax.experimental.pallas.tpu`` — lint rule R001 (``repro.analysis``)
enforces that every other module routes through these aliases.
"""
from __future__ import annotations

from jax.experimental.pallas import tpu as pltpu  # repro-lint: disable=R001

CompilerParams = pltpu.CompilerParams
MemorySpace = pltpu.MemorySpace

# Scratch-shape constructor for VMEM buffers: ``plc.VMEM((m, n), dtype)``.
VMEM = MemorySpace.VMEM
SMEM = MemorySpace.SMEM

# Grid spec with scalar prefetch (decode kernels' page tables).
PrefetchScalarGridSpec = pltpu.PrefetchScalarGridSpec
