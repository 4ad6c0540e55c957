#!/usr/bin/env python3
"""One traced window of a cell, read for the engine's own spans, counters
and program scopes; prints one JSON line.

    python3 benchmarks/chip/profile_engine.py --workload qwen2.5-3b.long-doc \\
        --seed 7 --seconds 10

The window is the one ``run.py --trace 1`` measures (``harness.serve``).
The line holds what ``run.py`` does not read yet (``engine_trace.py``):
the engine's counters and the slot use they give, host milliseconds per
cycle, idle seconds by the innermost host span, and the step programs'
device time by scope with the unscoped operations that take most of it;
beside them ``output_tok_s`` of the traced window and the cell's per-layer
metrics, read from the same trace.  Each operation's scope comes from the
step programs' optimized HLO, which XLA dumps while they compile, so this
run compiles them anew and keeps no compile cache.  No correctness check
runs.  Like ``run.py``, it needs the chip.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE), str(CHECKOUT / "src")]
    import engine_trace as E

    work = Path(tempfile.mkdtemp(prefix="engine_trace_"))
    trace_dir, dump_dir = work / "trace", work / "hlo"
    # a program loaded from a compile cache is not compiled, so not dumped
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    os.environ["XLA_FLAGS"] = " ".join(filter(None, (
        os.environ.get("XLA_FLAGS"), f"--xla_dump_to={dump_dir}",
        E.DUMP_FLAGS)))

    import jax

    import derive
    import harness
    import trace_reduce

    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("profile_engine.py: needs a TPU chip", file=sys.stderr)
        shutil.rmtree(work, ignore_errors=True)
        return 1
    peaks = json.loads((HERE / "peaks.json").read_text())

    # ``harness.serve`` frees its engine before it returns; keep it, to
    # read its counters after the window
    engines = []
    build = harness.build_engine

    def keep(*a):
        engines.append(build(*a))
        return engines[-1]

    harness.build_engine = keep
    try:
        run, _, _ = harness.serve(cell, args.seed, args.seconds,
                                  trace_dir=trace_dir, t_process=T_PROCESS,
                                  peaks=peaks)
        stats = engines.pop().stats()
        run.trace = trace_reduce.capture(trace_dir)
        ex = E.capture(trace_dir, E.read_dump(dump_dir))
    finally:
        shutil.rmtree(work, ignore_errors=True)
    tr = run.trace
    per_layer = {}
    for m in cell.per_layer:
        v = harness.load_reader(m["name"], cell.root)(run)
        if v is not None:
            per_layer[m["name"]] = v
    print(json.dumps({
        "workload": cell.name, "seed": args.seed,
        "window_s": run.window_s,
        "output_tok_s": derive.output_tokens(run) / run.window_s,
        "cycles": len(run.cycles),
        "engine": stats,
        "prefill_slot_use": E.slot_use_pct(stats, "prefill_positions",
                                           "prefill_slots"),
        "decode_row_use": E.slot_use_pct(stats, "decode_positions",
                                         "decode_row_steps"),
        "host_ms_per_cycle": E.host_ms_per_cycle(tr, ex),
        "programs_s": E.programs_s(tr),
        "scope_pct": {s: E.scope_pct(tr, ex, s) for s in E.SCOPES + ("",)},
        "unscoped_ops": E.unscoped_ops(tr, ex),
        "idle_s": trace_reduce.window_s(tr) - trace_reduce.busy_s(tr),
        "idle_by_span": E.idle_by_span(tr, ex),
        "per_layer": per_layer,
        "device_ops": trace_reduce.top_ops(tr),
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
