"""Arithmetic the metric readers share: the work of the window's requests
from the traffic (the family's ``window_work``), and device times from
the trace.

Every request of a window is submitted and completed inside it, so the
window's work is the whole work of its requests: each prompt fed in
pieces of ``prefill_chunk`` positions, then each generated token but the
last fed one at a time.  None of it depends on how the engine schedules
the pieces.
"""
from __future__ import annotations

import trace_reduce as tracemod

DECODE_PROGRAM = "_step_n"
PREFILL_PROGRAM = "_prefill_step"
ATTENTION_KERNELS = ("repro_flash_decode_paged", "repro_flash_prefill_chunk_paged")


def output_tokens(run) -> int:
    """Tokens the engine returned for the window's requests."""
    return sum(len(r.out) for r in run.done())


def window_work(run) -> dict | None:
    """Work of the window's requests by layer (the family's
    ``window_work``), or None where nothing was traced or a request did
    not complete."""
    if run.trace is None or not run.recs or len(run.done()) < len(run.recs):
        return None
    cell = run.cell
    dispatches = sum(c.prefill_steps + c.decode_steps for c in run.cycles)
    return cell.family.window_work(
        cell.arch, run.recs, cell.config["engine"]["prefill_chunk"],
        dispatches)


def peak(run) -> dict:
    kind = run.notes["device_kind"]
    if kind not in run.peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return run.peaks[kind]


def roofline_pct(run, works, kernels) -> float | None:
    """100 * least time for ``works`` / device time of ``kernels``."""
    t = tracemod.kernel_s(run.trace, *kernels)
    if t <= 0:
        return None
    pk = peak(run)
    least, bounds = 0.0, []
    for w in works:
        if w.flops or w.bytes:
            s, bound = w.bound_s(pk["bf16_flops_per_s"], pk["hbm_bytes_per_s"])
            least += s
            bounds.append(bound)
    run.notes.setdefault("bounds", {})["+".join(kernels)] = bounds
    return 100.0 * least / t if least > 0 else None


def step_mfu_pct(run) -> float | None:
    w = window_work(run)
    if w is None:
        return None
    t = tracemod.program_s(run.trace, DECODE_PROGRAM) + \
        tracemod.program_s(run.trace, PREFILL_PROGRAM)
    if t <= 0:
        return None
    return 100.0 * w["model_flops"] / (t * peak(run)["bf16_flops_per_s"])


def step_ms(run, program: str, counter: str) -> float | None:
    """Device milliseconds of ``program`` per step it ran in the window
    (``counter``: ``decode_steps`` or ``prefill_steps``)."""
    if run.trace is None:
        return None
    n = sum(getattr(c, counter) for c in run.cycles)
    t = tracemod.program_s(run.trace, program)
    return 1e3 * t / n if n and t > 0 else None


def idle_pct(run) -> float | None:
    if run.trace is None:
        return None
    win = tracemod.window_s(run.trace)
    if win <= 0:
        return None
    return 100.0 * (1.0 - tracemod.busy_s(run.trace) / win)
