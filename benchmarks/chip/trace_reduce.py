"""From a profiler trace to the numbers the per-layer metrics read.

:func:`capture` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into
a :class:`Trace` of plain tuples (device operations, device programs,
the harness's own host spans), and everything after that is arithmetic
on those tuples, which the tests check on a synthetic trace:

* busy time: the union of the intervals in which an operation ran on a
  device, inside the traced window, averaged over the devices;
* the device time of a kernel: the summed durations of the operations
  named after it (``%repro_gemm.79 = ...`` is an instance of
  ``repro_gemm``);
* the device time of a program: the summed durations of its events on
  the device's module line (``jit__step_n``, ``jit__prefill_step``);
* idle gaps: the stretches of the window with no operation on the
  device, each named by the harness span (``bench.step``,
  ``bench.submit``) that covers most of it.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
# operations that hold others on the same line: counted in busy time
# through what they hold, never on their own
CONTROL_FLOW = ("while", "conditional", "call")


def op_name(text: str) -> str:
    """``repro_gemm`` of ``%repro_gemm.79 = bf16[16,256] custom-call(...)``."""
    return re.sub(r"\.\d+$", "", text.split(" = ", 1)[0].lstrip("%"))


@dataclass
class Trace:
    """Intervals in nanoseconds on one clock."""
    ops: dict = field(default_factory=dict)       # device -> [(name, t0, t1)]
    modules: dict = field(default_factory=dict)   # device -> [(name, t0, t1)]
    host: list = field(default_factory=list)      # [(name, t0, t1)]
    window: tuple = (0, 0)                        # (t0, t1)


def capture(path: Path, window_ns: tuple | None = None) -> Trace:
    """Read the one ``.xplane.pb`` under ``path``.  ``window_ns`` is the
    traced window on the trace's clock; by default, the extent of the
    harness's host spans."""
    from jax.profiler import ProfileData

    files = sorted(Path(path).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    data = ProfileData.from_file(str(files[-1]))
    tr = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    tr.ops[plane.name] = [
                        (op_name(ev.name), ev.start_ns, ev.end_ns)
                        for ev in line.events]
                elif line.name == MODULES_LINE:
                    tr.modules[plane.name] = [
                        (ev.name, ev.start_ns, ev.end_ns)
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.host += [(ev.name, ev.start_ns, ev.end_ns)
                            for ev in line.events
                            if ev.name.startswith(HOST_PREFIX)]
    if window_ns is None and tr.host:
        window_ns = (min(h[1] for h in tr.host), max(h[2] for h in tr.host))
    tr.window = window_ns or (0, 0)
    return tr


def _clip(intervals, w0, w1):
    for t0, t1 in intervals:
        a, b = max(t0, w0), min(t1, w1)
        if b > a:
            yield a, b


def union(intervals) -> list:
    """Merged, sorted intervals."""
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_s(tr: Trace) -> float:
    """Seconds with an operation running, averaged over the devices."""
    if not tr.ops:
        return 0.0
    w0, w1 = tr.window
    tot = 0.0
    for evs in tr.ops.values():
        tot += sum(b - a for a, b in
                   union(_clip(((e[1], e[2]) for e in evs), w0, w1)))
    return tot / len(tr.ops) / 1e9


def window_s(tr: Trace) -> float:
    return (tr.window[1] - tr.window[0]) / 1e9


def kernel_s(tr: Trace, *kernels: str) -> float:
    """Device seconds of the operations of ``kernels``, averaged over the
    devices, inside the window."""
    if not tr.ops:
        return 0.0
    w0, w1 = tr.window
    tot = 0.0
    for evs in tr.ops.values():
        tot += sum(b - a for a, b in _clip(
            ((e[1], e[2]) for e in evs if e[0] in kernels),
            w0, w1))
    return tot / len(tr.ops) / 1e9


def program_s(tr: Trace, program: str) -> float:
    """Device seconds of the programs whose module name holds
    ``program``, averaged over the devices, inside the window."""
    if not tr.modules:
        return 0.0
    w0, w1 = tr.window
    tot = 0.0
    for evs in tr.modules.values():
        tot += sum(b - a for a, b in _clip(
            ((e[1], e[2]) for e in evs if program in e[0]), w0, w1))
    return tot / len(tr.modules) / 1e9


def top_ops(tr: Trace, n: int = 10) -> list:
    """The ``n`` operation names with the most device seconds, averaged
    over the devices; control flow is left out."""
    w0, w1 = tr.window
    tot: dict = {}
    for evs in tr.ops.values():
        for e in evs:
            a, b = max(e[1], w0), min(e[2], w1)
            if b > a and e[0] not in CONTROL_FLOW:
                tot[e[0]] = tot.get(e[0], 0.0) + (b - a) / 1e9
    k = max(len(tr.ops), 1)
    return sorted(([name, s / k] for name, s in tot.items()),
                  key=lambda x: -x[1])[:n]


def idle_gaps(tr: Trace, n: int = 10) -> list:
    """The ``n`` longest stretches with no operation on the first device,
    each as ``[host span covering most of it, seconds]``."""
    if not tr.ops:
        return []
    w0, w1 = tr.window
    evs = tr.ops[sorted(tr.ops)[0]]
    busy = union(_clip(((e[1], e[2]) for e in evs), w0, w1))
    gaps, t = [], w0
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if w1 > t:
        gaps.append((t, w1))
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        cover: dict = {}
        for name, h0, h1 in tr.host:
            ov = min(b, h1) - max(a, h0)
            if ov > 0:
                cover[name] = cover.get(name, 0) + ov
        label = max(cover, key=cover.get) if cover else "no harness span"
        out.append([label, (b - a) / 1e9])
    return out
