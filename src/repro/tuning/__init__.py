"""Autotuning harness — per-device kernel tuning as a committed artifact.

The paper's core lesson is that "code once, target many devices" only
pays off when per-device tuning is cheap and systematic: PHAST exposes
per-kernel tuning knobs exactly so the same source can be re-tuned per
architecture.  This package is that mechanism for the Pallas lowerings,
in the queryop idiom (enumerate every op x backend registration cell,
persist the result as a committed artifact):

    python -m repro.tuning.autotune          sweep, write the table
    src/repro/tuning/tuning_table.json       the committed result
    benchmarks/perf_snapshot.py              BENCH_*.json trajectory

Tuning-key / shape-class convention
-----------------------------------

Every op with a Pallas lowering declares its tuning key(s) at
``register_op(..., tuning=...)`` (enforced by the C102/C103 coverage
lint).  The kernel resolves its knobs at trace time as

    t = get_tuning("<key>", key=shape_class(<dims>), <knob>=<default>, ...)

where ``shape_class`` (:mod:`repro.tuning.shapes`) buckets each
classified dimension to the next power of two and joins them into a
canonical string (``"k256.m64.n256"``).  Which dims a key classifies is
part of its contract — the autotuner's cell drivers mirror the kernel
call sites and the sweep asserts agreement via
``registry.last_resolved``:

    key                 classified dims
    ------------------  ------------------------------------------
    gemm                m, n, k            (matmul / conv im2col GEMM)
    bias_add, relu      m, n               (flattened 2-D tile)
    conv_direct         c, f               (in/out channels)
    rmsnorm             d, r               (feature dim, rows)
    softmax             r, v               (rows, vocab/row width)
    softmax_xent        b, v               (batch rows, vocab)
    flash_attention     d, s               (head dim, sequence)
    flash_decode        s                  (max cache length)
    flash_prefill       c, s               (chunk width, max cache length)
    ssd_scan            s                  (sequence length)
    ssd_prefill_chunk   s                  (serving chunk width)

Resolution precedence (lowest to highest; ``repro.core.registry``):

    call-site defaults
        < table (op, "default")  < table (op, shape_class)
        < set_tuning (op, "default") < set_tuning (op, shape_class)

i.e. the persisted table supersedes the hand-set call-site defaults for
every shape class it covers, while an explicit ``set_tuning`` override
(tests, experiments) always beats the table.  A ``key=`` lookup that
misses every layer falls back cleanly to the call-site defaults.
gemm's call-site defaults are not literals but the blocks it plans from
each call's shape (``kernels.gemm.plan_blocks``, recorded in
``registry.resolved_plans``); the autotuner sweeps around that plan.

Table format (``tuning_table.json``, schema 1)
----------------------------------------------

    {
      "schema": 1,
      "backend": "pallas",
      "environment": {"platform": "cpu", "interpret": true, ...},
      "cells":  [ {"op": ..., "status": "swept|no-knobs|reference_only",
                   ...}, ... ],            # full queryop-style enumeration
      "entries": {
        "<tuning key>": {
          "<shape class>": {
            "params": {"<knob>": <int>, ...},   # what get_tuning resolves
            "ms": <best candidate ms>,
            "default_ms": <call-site-default ms>,
            "speedup": <default_ms / ms>,
            "ops": ["<registered ops declaring this key>", ...]
          }
        }
      }
    }

``entries`` is what ``get_tuning`` reads (flattened by
:func:`repro.tuning.table.flatten`); ``cells`` is the audit trail — every
registered op appears exactly once with the reason it was or wasn't
swept.  The table is validated against the live registry by the C104/
C105 coverage lint: an entry whose op lost its Pallas lowering, or whose
params name a knob no kernel call site resolves anymore, fails
``scripts/ci.sh --lint``.

The sweep space is *derived*, not hand-listed: knob names and their
hand-set defaults are AST-scanned from the ``get_tuning`` call sites
under ``src/repro/kernels`` (the same scan the C103 lint uses), and
candidates are the power-of-two ladder around each default.  Sweeps pin
the backend with scoped ``use_backend("pallas")`` (R004: never
``set_default_backend`` in library code) and reject candidates that
retrace — a value is recorded only if repeated calls hit the jit cache.

``REPRO_TUNING_TABLE=<path>`` points the registry at a different table;
``REPRO_TUNING_TABLE=`` (empty) disables table loading entirely.
"""
from repro.tuning.shapes import bucket, parse_shape_class, shape_class
from repro.tuning.table import (
    SCHEMA_VERSION,
    default_path,
    flatten,
    load,
    save,
    validate,
)

__all__ = [
    "bucket",
    "parse_shape_class",
    "shape_class",
    "SCHEMA_VERSION",
    "default_path",
    "flatten",
    "load",
    "save",
    "validate",
]
