#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print one JSON result line.

    python3 benchmarks/chip/run.py --workload qwen2.5-3b.long-doc \\
        --seed 7 --seconds 30 --trace 0

With ``--trace 0`` the line holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  The last stderr lines and the result's ``checks`` key give every
number the correctness check compared, beside its limit.

The run needs a TPU with Mosaic-compiled Pallas kernels and as many chips
as the cell asks for; otherwise it exits non-zero and prints no result.
JAX's persistent compilation cache lives in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
CACHE_DIR = CHECKOUT / ".jax_cache"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if not (CHECKOUT / "src" / "repro").is_dir():
        print(f"run.py: no program under test at {CHECKOUT / 'src'}",
              file=sys.stderr)
        return 2
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path[:0] = [str(HERE), str(CHECKOUT / "src")]

    import jax

    from repro.core.compile_cache import enable_compile_cache
    from repro.core.policy import interpret_default

    import harness

    cell = harness.load_cell(args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or interpret_default() \
            or len(devs) < cell.chips:
        print(f"run.py: needs {cell.chips} TPU chip(s) with Mosaic-compiled "
              f"Pallas kernels; JAX found {len(devs)} {devs[0].platform!r} "
              f"device(s)", file=sys.stderr)
        return 1
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    enable_compile_cache()
    peaks = json.loads((HERE / "peaks.json").read_text())
    if devs[0].device_kind not in peaks:
        print(f"run.py: no peaks for device kind {devs[0].device_kind!r}",
              file=sys.stderr)
        return 1
    out = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), t_process=T_PROCESS,
        peaks=peaks, log=lambda s: print(s, file=sys.stderr))
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
