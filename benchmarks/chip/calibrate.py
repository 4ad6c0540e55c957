#!/usr/bin/env python3
"""The readings that set a cell's check limit; not part of a benchmark run.

    python3 benchmarks/chip/calibrate.py --workload W --seeds 1,2,3 \\
        --seconds 10 [--limit 0.5]

Serves the cell once per seed in one process, as a run does, and prints
one JSON line per seed: the harness's check of the program's served
tokens, and the same check with the control in the program's place (the
tokens that the reference in the next lower precision ranks first at the
same positions: float8 weights for a bfloat16 configuration, bfloat16 for
a float32 one).  The limit lies between the program's largest and the
control's smallest widest gap; each line also says whether the program
and the control come out correct under ``--limit`` (by default the
cell's).  Needs the chip, as ``run.py`` does.
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

CONTROL = {"bfloat16": "fp8", "float32": "bf16"}


def readings(cell, seed: int, seconds: float, peaks: dict,
             limit: float) -> dict:
    """The program's and the control's check for one seed."""
    import harness

    run, params, _ = harness.serve(cell, seed, seconds, trace_dir=None,
                                   t_process=time.perf_counter(), peaks=peaks)
    t0 = time.perf_counter()
    prog = harness.check(run, params, limit)
    t1 = time.perf_counter()
    ctrl = harness.check(run, params, limit, CONTROL[cell.arch.dtype])
    del params
    return {"seed": seed, "limit": limit,
            "program_gap": prog["checks"]["max_gap"]["value"],
            "control_gap": ctrl["checks"]["max_gap"]["value"],
            "program_correct": prog["correct"],
            "control_correct": ctrl["correct"],
            "served": prog["checked"], "requests": prog["requests_checked"],
            "program_argmax": prog["argmax"], "control_argmax": ctrl["argmax"],
            "window_s": run.window_s, "check_s": t1 - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--limit", type=float, default=None)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CHECKOUT / ".jax_cache")
    sys.path[:0] = [str(HERE), str(CHECKOUT / "src")]
    import jax

    from repro.core.compile_cache import enable_compile_cache
    from repro.core.policy import interpret_default

    import harness

    if jax.devices()[0].platform != "tpu" or interpret_default():
        print("calibrate.py: needs a TPU", file=sys.stderr)
        return 1
    enable_compile_cache()
    peaks = json.loads((HERE / "peaks.json").read_text())
    cell = harness.load_cell(args.workload)
    limit = (args.limit if args.limit is not None
             else float(cell.params["max_gap_limit"]))
    for s in args.seeds.split(","):
        print(json.dumps({"cell": cell.name, **readings(
            cell, int(s), args.seconds, peaks, limit)}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
