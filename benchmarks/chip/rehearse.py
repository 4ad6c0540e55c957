#!/usr/bin/env python3
"""Compile each cell's engine programs for a described TPU v5e, without
the chip, and print what they hold in device memory.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py [cell ...]

For every cell (all of ``BENCHMARK.json`` by default) this builds the
engine over shapes only (the program's decode state from
``jax.eval_shape``, the benchmark's weights likewise), lowers its
admission, chunked-prefill, fused-decode and release programs with every
argument placed on one described chip and Pallas kernels compiled by
Mosaic, and prints ``memory_analysis()`` per program beside the bytes of
the parameters and of the decode state.  A program the chip's compiler
refuses fails here.  Nothing runs; no number here is a chip measurement.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]


def _nbytes(tree) -> int:
    import jax
    import numpy as np
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves(tree))


def rehearse(name: str, one_chip) -> dict:
    import jax
    import jax.numpy as jnp

    import harness
    import weights
    from repro.core.policy import use_backend

    cell = harness.load_cell(name)
    model = harness.build_program(cell)
    params = jax.eval_shape(lambda: weights.make(cell.arch, 0))
    shape_model = dataclasses.replace(
        model, init_decode_state=lambda *a, **k: jax.eval_shape(
            lambda: model.init_decode_state(*a, **k)))

    def place(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            tree)

    out = {"param_bytes": _nbytes(params)}
    with use_backend("pallas"):
        eng = harness.build_engine(cell, shape_model, params)
        out["decode_state_bytes"] = _nbytes(eng._mstate)
        ps, ms, sl = place(params), place(eng._mstate), place(eng._slots)
        b, L = cell.mix["rows"], cell.mix["max_len"]
        vec = lambda dt: jax.ShapeDtypeStruct((b,), dt, sharding=one_chip)
        progs = {
            "_step_n": (eng._step_n, (ps, ms, sl, vec(jnp.bool_))),
            "_prefill": (eng._prefill, (ps, ms, sl)),
            "_admit": (eng._admit, (
                ms, sl, jax.ShapeDtypeStruct((b, L), jnp.int32, sharding=one_chip),
                vec(jnp.int32), vec(jnp.int32),
                jax.ShapeDtypeStruct((b, 2), jnp.uint32, sharding=one_chip),
                vec(jnp.bool_), vec(jnp.int32), vec(jnp.int32), vec(jnp.int32))),
            "_release": (eng._release, (ms, sl, vec(jnp.bool_))),
        }
        for pname, (fn, args) in progs.items():
            mem = fn.lower(*args).compile().memory_analysis()
            out[pname] = {k: int(getattr(mem, k)) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes")}
    return out


def main(argv) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(HERE), str(CHECKOUT / "src")]
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    import repro.kernels.ops  # noqa: F401  (load every kernel module)
    for mod in [m for k, m in sys.modules.items()
                if k.startswith("repro.kernels")]:
        if hasattr(mod, "interpret_default"):
            mod.interpret_default = lambda: False
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    for name in argv or [w["name"] for w in bench["workloads"]]:
        print(json.dumps({"cell": name, **rehearse(name, one_chip)}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
