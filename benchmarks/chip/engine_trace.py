"""The engine's own spans, counters and program scopes, read from a run.

The program under test names the host's part of each serving cycle with
``engine.*`` profiler spans (``ServingEngine.step``: ``engine.step`` and,
inside it, ``engine.refill``, ``engine.prefill``, ``engine.decode``,
``engine.harvest``, ``engine.release``), stages every operation of its
step programs under a name scope (``SCOPES``), and counts the token slots
and decode rows its programs run and use (``ServingEngine.stats``).
:func:`capture` reads the spans and the step programs' device operations
from the same ``.xplane.pb`` that ``trace_reduce.capture`` reads.  An
``XLA Ops`` event names its HLO instruction but holds no name stack (on a
TPU v5e its stats are the device offset, duration and time scale), so
each operation's scope comes from the op metadata of the compiled module
(:func:`hlo_stacks`, over the optimized HLO text that XLA dumps when
``--xla_dump_to`` is set), matched by module and instruction name.
Everything after that is arithmetic on plain tuples, which the tests
check on a synthetic trace.  A program without the spans, scopes or
counters gives empty lists and missing keys, and every reading is then
None.

* host milliseconds per cycle: each ``engine.step`` span less the
  ``engine.harvest`` spans inside it, where the host blocks on the device;
* device time by scope: the operations of the step programs, each under
  the innermost scope of its name stack ("" for none), over the programs'
  device time;
* idle time by span: each stretch with no operation on the device, cut
  where a host span starts or ends, under the innermost span covering it;
* slot use: positions the live rows advanced over the token slots (the
  chunked-prefill program) or row-steps (the fused decode program) run.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from pathlib import Path

import trace_reduce as T

ENGINE_PREFIX = "engine."
SCOPES = ("embed", "layers", "attention", "kv_pool", "mlp", "ssm",
          "lm_head", "sample")
PROGRAMS = ("_step_n", "_prefill_step")
# XLA flags that dump the step programs' optimized HLO, op metadata
# included, into a directory (appended with ``--xla_dump_to=<dir>``)
DUMP_FLAGS = ("--xla_dump_hlo_as_text "
              "--xla_dump_hlo_module_re=.*(" + "|".join(PROGRAMS) + ").*")
_INSTR = re.compile(
    r'^\s*(?:ROOT )?%([^ ]+) = .*metadata=\{op_name="([^"]*)"')


@dataclass
class Extra:
    """Intervals in nanoseconds, on the clock of the ``Trace`` beside it."""
    spans: list = field(default_factory=list)    # [(name, t0, t1)] engine.*
    # device -> [(scope, name, t0, t1)], the step programs' operations
    scoped: dict = field(default_factory=dict)


def scope_of(stack: str) -> str:
    """The innermost of ``SCOPES`` in an op's name stack
    (``jit(_step_n)/while/body/layers/while/body/attention/kv_pool/
    transpose`` is ``kv_pool``), or "" where there is none."""
    for part in reversed(stack.split("/")):
        if part in SCOPES:
            return part
    return ""


def hlo_stacks(text: str) -> dict:
    """``{module: {instruction: name stack}}`` from optimized HLO text (one
    or more modules, as ``compile().as_text()`` or an XLA dump gives it)."""
    out: dict = {}
    mod: dict = {}
    for line in text.splitlines():
        if line.startswith("HloModule "):
            mod = out.setdefault(line.split()[1].rstrip(","), {})
        else:
            m = _INSTR.match(line)
            if m:
                mod[m.group(1)] = m.group(2)
    return out


def read_dump(path: Path) -> dict:
    """:func:`hlo_stacks` of every optimized module XLA dumped under
    ``path``."""
    out: dict = {}
    for f in sorted(Path(path).rglob("*after_optimizations.txt")):
        out.update(hlo_stacks(f.read_text()))
    return out


def capture(path: Path, stacks: dict) -> Extra:
    """Read the one ``.xplane.pb`` under ``path`` for ``engine.*`` host
    spans and the step programs' device operations, each with its scope
    from ``stacks`` (:func:`read_dump`)."""
    from jax.profiler import ProfileData

    files = sorted(Path(path).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    data = ProfileData.from_file(str(files[-1]))
    ex = Extra()
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: list(line.events) for line in plane.lines}
            runs = sorted((ev.start_ns, ev.end_ns, ev.name.split("(")[0])
                          for ev in lines.get(T.MODULES_LINE, [])
                          if any(p in ev.name for p in PROGRAMS))
            starts = [r[0] for r in runs]
            ops = ex.scoped[plane.name] = []
            for ev in lines.get(T.OPS_LINE, []):
                i = bisect.bisect_right(starts, ev.start_ns) - 1
                if i < 0 or ev.end_ns > runs[i][1]:
                    continue
                instr = ev.name.split(" = ", 1)[0].lstrip("%")
                stack = stacks.get(runs[i][2], {}).get(instr, "")
                ops.append((scope_of(stack), T.op_name(ev.name),
                            ev.start_ns, ev.end_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                ex.spans += [(ev.name, ev.start_ns, ev.end_ns)
                             for ev in line.events
                             if ev.name.startswith(ENGINE_PREFIX)]
    return ex


def host_ms_per_cycle(tr: T.Trace, ex: Extra) -> float | None:
    """Mean over the window's cycles of the host's own milliseconds:
    ``engine.step`` less the ``engine.harvest`` spans inside it."""
    w0, w1 = tr.window
    steps = [s for s in ex.spans
             if s[0] == "engine.step" and s[1] >= w0 and s[2] <= w1]
    if not steps:
        return None
    harvest = sorted((s[1], s[2]) for s in ex.spans
                     if s[0] == "engine.harvest")
    starts = [h[0] for h in harvest]
    tot = 0
    for _, a, b in steps:
        i = bisect.bisect_left(starts, a)
        blocked = 0
        while i < len(harvest) and harvest[i][1] <= b:
            blocked += harvest[i][1] - harvest[i][0]
            i += 1
        tot += (b - a) - blocked
    return tot / len(steps) / 1e6


def _program_ops(tr: T.Trace, ex: Extra):
    """``(scope, name, seconds)`` of each operation of the step programs
    inside the window; control flow is left out, as in
    ``trace_reduce.top_ops``."""
    w0, w1 = tr.window
    for evs in ex.scoped.values():
        for scope, name, t0, t1 in evs:
            a, b = max(t0, w0), min(t1, w1)
            if b > a and name not in T.CONTROL_FLOW:
                yield scope, name, (b - a) / 1e9


def _by(tr: T.Trace, ex: Extra, key) -> dict:
    tot: dict = {}
    for op in _program_ops(tr, ex):
        k = key(op)
        if k is not None:
            tot[k] = tot.get(k, 0.0) + op[2]
    n = max(len(ex.scoped), 1)
    return {k: v / n for k, v in tot.items()}


def scope_s(tr: T.Trace, ex: Extra) -> dict:
    """Device seconds of the step programs' operations by scope ("" for
    none), averaged over the devices."""
    return _by(tr, ex, lambda op: op[0])


def programs_s(tr: T.Trace) -> float:
    return sum(T.program_s(tr, p) for p in PROGRAMS)


def scope_pct(tr: T.Trace, ex: Extra, scope: str) -> float | None:
    """100 x device time of ``scope`` / device time of the step programs;
    None where no operation carries a scope."""
    by = scope_s(tr, ex)
    t = programs_s(tr)
    if t <= 0 or not any(by.get(s) for s in SCOPES):
        return None
    return 100.0 * by.get(scope, 0.0) / t


def unscoped_ops(tr: T.Trace, ex: Extra, n: int = 10) -> list:
    """The ``n`` operation names of the step programs with no scope and the
    most device seconds, as ``[name, seconds]``."""
    by = _by(tr, ex, lambda op: None if op[0] else op[1])
    return sorted(([k, v] for k, v in by.items()), key=lambda x: -x[1])[:n]


def idle_by_span(tr: T.Trace, ex: Extra) -> dict:
    """Idle seconds of the first device by the innermost host span
    covering them (harness ``bench.*`` and engine ``engine.*`` spans; the
    latest-starting covering span is the innermost), "no span" where none
    covers them."""
    if not tr.ops:
        return {}
    w0, w1 = tr.window
    evs = tr.ops[sorted(tr.ops)[0]]
    busy = T.union(T._clip(((e[1], e[2]) for e in evs), w0, w1))
    gaps, t = [], w0
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if w1 > t:
        gaps.append((t, w1))
    spans = sorted(list(tr.host) + list(ex.spans), key=lambda s: (s[1], -s[2]))
    cuts = sorted({t for s in spans for t in (s[1], s[2])} | {w0, w1})
    # the innermost covering span of each stretch between two cuts
    label, open_ = [], []
    j = 0
    for a, b in zip(cuts, cuts[1:]):
        while j < len(spans) and spans[j][1] <= a:
            open_.append(spans[j])
            j += 1
        open_ = [s for s in open_ if s[2] > a]
        label.append(max(open_, key=lambda s: (s[1], -s[2]))[0]
                     if open_ else "no span")
    out: dict = {}
    for a, b in gaps:
        i = max(bisect.bisect_right(cuts, a) - 1, 0)
        while i < len(label) and cuts[i] < b:
            ov = min(b, cuts[i + 1]) - max(a, cuts[i])
            if ov > 0:
                out[label[i]] = out.get(label[i], 0.0) + ov / 1e9
            i += 1
    return dict(sorted(out.items(), key=lambda x: -x[1]))


def slot_use_pct(stats: dict, positions: str, slots: str) -> float | None:
    """100 x ``stats[positions]`` / ``stats[slots]`` of the engine's
    counters; None where the program does not count them."""
    n = stats.get(slots)
    if not n or positions not in stats:
        return None
    return 100.0 * stats[positions] / n
