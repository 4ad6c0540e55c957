"""One run of one cell: set-up, the measured window, the check.

Everything a cell is made of is found by name under the benchmark's
directory: ``BENCHMARK.json`` (one level up from ``benchmarks/``) names
the cell's configuration and traffic mix; ``configs/<config>.json`` holds
the sizes and the engine's settings, ``families/<family>.py`` the code
for the configuration's ``family`` (its sizes, weight table, reference and
work; ``arch.py`` lists what it provides), ``traffic/<mix>.json`` the
mix's parameters, ``cells/<cell>.json`` what belongs to the pair alone
(the check's limit), and ``metrics/<metric>.py`` one reader per metric.
Adding a cell, a mix, a configuration, a family or a metric adds files
and entries and edits none.

The window: the mix's clients submit requests (each client its next
request as soon as its last one completes) for ``--seconds``, or, where
the mix states ``requests``, until that many have been submitted; then
they submit no more, and the window lasts until every request it took
has completed.  So every output token of the window is one the engine
returned, and every request is finished and can be checked.

The engine is driven through its public surface only (``submit``,
``step``, ``outputs``, ``steps``, ``prefill_steps``), under the
Pallas backend.  The harness marks its own calls with
``jax.profiler.TraceAnnotation`` (``bench.submit``, ``bench.step``) so a
traced run can name what the host did in each idle gap of the device.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp

import reference
import trace_reduce as tracemod
import weights
from arch import load_config, load_family
from traffic import Traffic, load_mix

HERE = Path(__file__).resolve().parent
# served tokens the check compares, at the least: most of a long-doc
# window's requests, one per engine row
SERVED_CHECKED = 2048


# --------------------------------------------------------------------------
# finding a cell
# --------------------------------------------------------------------------

@dataclass
class Cell:
    root: Path            # the benchmark's directory
    name: str
    chips: int
    config_name: str
    config: dict
    family: object        # the module families/<family>.py
    arch: object          # the family's Arch
    traffic_name: str
    mix: dict
    params: dict          # cells/<name>.json
    end_to_end: list      # metric entries of BENCHMARK.json
    per_layer: list


def load_cell(name: str, root: Path = HERE, bench: dict | None = None) -> Cell:
    """Resolve workload ``name`` of ``BENCHMARK.json`` into its files."""
    if bench is None:
        bench = json.loads((root.parents[1] / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    config = load_config(w["config"], root)
    family = load_family(config["family"], root)
    params = json.loads((root / "cells" / f"{name}.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return Cell(root, name, int(w["chips"]), w["config"], config, family,
                family.arch_of(config), w["traffic"],
                load_mix(w["traffic"], root), params, e2e, per_layer)


def load_reader(metric: str, root: Path = HERE):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    path = root / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {metric!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------------------
# the program under test
# --------------------------------------------------------------------------

def build_program(cell: Cell):
    """The program's model for the cell's configuration, checked against
    the sizes the configuration file states."""
    from repro.configs.registry import get_arch
    from repro.models.model import build_model

    cfg = get_arch(cell.config["arch"])
    want = cell.family.program_sizes(cell.arch)
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"the program's {cfg.name} differs from "
                         f"{cell.config_name}.json: {got} != {want}")
    return build_model(cfg)


def make_weights(cell: Cell, model, seed: int):
    """The benchmark's weights, checked leaf by leaf against the shapes
    and dtypes the program's own initializer gives."""
    params = weights.make(cell.family.shapes, cell.arch, seed)
    want = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    got = {p: (x.shape, x.dtype) for p, x in weights.flat(params).items()}
    ref = {p: (x.shape, x.dtype) for p, x in weights.flat(want).items()}
    if got != ref:
        raise ValueError(f"weight layout differs from the program's: "
                         f"{sorted(set(got.items()) ^ set(ref.items()))[:6]}")
    return jax.block_until_ready(params)


def engine_configs(engine: dict):
    """``(CacheConfig, EngineConfig)`` of a configuration's ``engine``
    object: each key sets the field of that name (``kv_layout`` sets
    ``layout``; ``note`` is prose), and any other key stops the run."""
    from dataclasses import fields

    from repro.serving import CacheConfig, EngineConfig

    kw: dict = {CacheConfig: {}, EngineConfig: {}}
    for key, value in engine.items():
        if key == "note":
            continue
        name = "layout" if key == "kv_layout" else key
        cls = next((c for c in kw if name in {f.name for f in fields(c)}),
                   None)
        if cls is None:
            raise ValueError(f"unknown engine key {key!r}: no CacheConfig "
                             f"or EngineConfig field of that name")
        kw[cls][name] = value
    return CacheConfig(**kw[CacheConfig]), EngineConfig(**kw[EngineConfig])


def build_engine(cell: Cell, model, params):
    from repro.serving import ServingEngine

    cache, config = engine_configs(cell.config["engine"])
    return ServingEngine(model, params, batch=cell.mix["rows"],
                         max_len=cell.mix["max_len"], cache=cache,
                         config=config)


# --------------------------------------------------------------------------
# the serving loop
# --------------------------------------------------------------------------

@dataclass
class Rec:
    index: int          # request index in the traffic
    plen: int
    max_new: int
    prompt: np.ndarray
    t_sub: float = 0.0  # host clock
    t_done: float | None = None
    out: np.ndarray | None = None


@dataclass
class Cycle:
    prefill_steps: int
    decode_steps: int


@dataclass
class Run:
    """What a run measured; the metric readers read this."""
    cell: Cell
    seed: int
    peaks: dict
    setup_s: float = 0.0
    t_start: float = 0.0
    t_end: float = 0.0
    recs: list = field(default_factory=list)      # every request, in order
    cycles: list = field(default_factory=list)    # every cycle of the window
    trace: tracemod.Trace | None = None
    notes: dict = field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return self.t_end - self.t_start

    def done(self) -> list:
        return [r for r in self.recs if r.out is not None]


class Driver:
    """Feeds one engine from one traffic, cycle by cycle."""

    def __init__(self, eng, traffic: Traffic, run: Run) -> None:
        self.eng, self.traffic, self.run = eng, traffic, run
        self.by_eid: dict = {}      # engine request id -> Rec
        self.in_flight: set = set()

    def submit(self) -> Rec:
        req = self.traffic.request(len(self.run.recs))
        rec = Rec(req.index, len(req.prompt), req.max_new, req.prompt)
        with jax.profiler.TraceAnnotation("bench.submit"):
            rec.t_sub = time.perf_counter()
            eid = int(self.eng.submit(req.prompt.tolist(), req.max_new))
        self.by_eid[eid] = rec
        self.in_flight.add(eid)
        self.run.recs.append(rec)
        return rec

    def cycle(self) -> None:
        eng = self.eng
        p0, d0 = eng.prefill_steps, eng.steps
        with jax.profiler.TraceAnnotation("bench.step"):
            eng.step()
        t1 = time.perf_counter()
        self.run.cycles.append(Cycle(eng.prefill_steps - p0, eng.steps - d0))
        for eid in [e for e in self.in_flight if e in eng.outputs]:
            rec = self.by_eid[eid]
            rec.t_done, rec.out = t1, eng.outputs[eid]
            self.in_flight.discard(eid)

    def closed(self, clients: int, until: float, max_cycles: int,
               requests: int | None = None) -> None:
        """Keep ``clients`` requests in flight until ``until``, or, with
        ``requests``, until that many have been submitted; then run
        cycles until none is, or ``max_cycles`` more have run (a request
        that never completes is then left for the check to count)."""
        def room() -> bool:
            return requests is None or len(self.run.recs) < requests

        while (time.perf_counter() < until if requests is None else room()):
            while len(self.in_flight) < clients and room():
                self.submit()
            self.cycle()
        for _ in range(max_cycles):
            if not self.in_flight:
                return
            self.cycle()


def warm_up(eng, vocab: int, chunk: int, seed: int) -> None:
    """Compile every program the window calls (admission, chunked
    prefill, fused decode, release) with one request that is admitted,
    prefilled, decoded and released in a single cycle."""
    rng = np.random.default_rng([seed, 3])
    eng.submit(rng.integers(0, vocab, chunk + 1).tolist(), 1)
    eng.step()
    if eng.queue or not eng.outputs:
        raise RuntimeError("warm-up request did not complete in one cycle")
    eng.reset_stats()


class CompileCount:
    """Backend compiles while open, from JAX's monitoring events (after
    ``chip_smoke.CompileLog``): the window should see none."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.n = 0

    def _on(self, event: str, duration: float, **_) -> None:
        self.n += event == self.EVENT

    def __enter__(self) -> "CompileCount":
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)


def start_trace(trace_dir: Path) -> None:
    """Device and harness spans only: the Python function tracer would
    slow the host loop it is meant to observe."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def drain_cycles(cell: Cell) -> int:
    """Cycles after the last submission by which every request in flight
    has completed: a prefill step or a decode step advances a row by one
    position at least, and a cycle decodes ``steps_per_sync`` of them."""
    mix = cell.mix
    longest = mix["prompt"]["hi"] + mix["output"]["hi"]
    return 2 * -(-longest // cell.config["engine"]["steps_per_sync"])


def serve(cell: Cell, seed: int, seconds: float, *, trace_dir: Path | None,
          t_process: float, peaks: dict):
    """Set up, run the window, and return ``(run, params, device peak)``
    with the engine released."""
    from repro.core.policy import use_backend

    run = Run(cell, seed, peaks)
    run.notes["device_kind"] = jax.devices()[0].device_kind
    # start-up: process start, imports, the runtime's start on the device
    marks = [("process", t_process), ("start-up", time.perf_counter())]
    model = build_program(cell)
    params = make_weights(cell, model, seed)
    marks.append(("weights", time.perf_counter()))
    traffic = Traffic(cell.mix, cell.arch.vocab, seed)
    with use_backend("pallas"):
        eng = build_engine(cell, model, params)
        marks.append(("engine", time.perf_counter()))
        warm_up(eng, cell.arch.vocab, cell.config["engine"]["prefill_chunk"],
                seed)
        marks.append(("warm-up", time.perf_counter()))
        drv = Driver(eng, traffic, run)
        run.t_start = time.perf_counter()
        run.setup_s = run.t_start - t_process
        if trace_dir is not None:
            start_trace(trace_dir)
        with CompileCount() as compiles:
            drv.closed(cell.mix["clients"], run.t_start + seconds,
                       drain_cycles(cell), cell.mix.get("requests"))
        run.t_end = time.perf_counter()
        run.notes["compiles_in_window"] = compiles.n
        # the K/V the window's requests held at the most, against the pool
        st = eng.stats()
        run.notes["kv_live_peak"] = {k: int(st[k]) for k in (
            "kv_pages_peak", "kv_pages", "kv_resident_bytes_peak") if k in st}
        if trace_dir is not None:
            jax.profiler.stop_trace()
        run.notes["setup_phases_s"] = {
            b[0]: round(b[1] - a[1], 3) for a, b in zip(marks, marks[1:])}
        dev_peak = peak_bytes(jax.devices()[0])
        del eng, drv
        gc.collect()
    return run, params, dev_peak


# --------------------------------------------------------------------------
# the check
# --------------------------------------------------------------------------

def buckets(max_len: int, step: int = 512) -> list:
    return sorted({min(b, max_len) for b in range(step, max_len + step, step)})


def sample(run: Run) -> list:
    """Requests the run finished, drawn from the seed, the one with the
    longest sequence first, until ``SERVED_CHECKED`` served tokens."""
    done = run.done()
    if not done:
        return []
    longest = max(done, key=lambda r: (r.plen + r.max_new, r.index))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([run.seed, 4]).permutation(len(rest))
    picked, served = [longest], longest.max_new
    for i in order:
        if served >= SERVED_CHECKED:
            break
        picked.append(rest[i])
        served += rest[i].max_new
    return picked


def gaps(family, arch, params, recs: list, max_len: int, out_hi: int,
         quantize: str = "") -> list:
    """Per sampled request, the reference's gaps at its served tokens;
    with ``quantize``, the gaps of the tokens the lower-precision
    reference ranks first at the same positions instead (the control)."""
    out = []
    for r in recs:
        toks = np.concatenate([r.prompt, r.out]).astype(np.int32)
        width = next(b for b in buckets(max_len) if b >= len(toks))
        feed = np.zeros((width,), np.int32)
        feed[:len(toks)] = toks
        pos = np.full((out_hi,), r.plen + r.max_new - 2, np.int32)
        pos[:r.max_new] = np.arange(r.plen - 1, r.plen + r.max_new - 1)
        want = family.logits(arch, params, jnp.asarray(feed),
                             jnp.asarray(pos))[:r.max_new]
        if quantize:
            got = family.logits(arch, params, jnp.asarray(feed),
                                jnp.asarray(pos), quantize)[:r.max_new]
            chosen = jnp.argmax(got, axis=1).astype(jnp.int32)
        else:
            chosen = jnp.asarray(r.out.astype(np.int32))
        out.append(np.asarray(reference.gaps(want, chosen)))
    return out


def check(run: Run, params, limit: float, quantize: str = "") -> dict:
    """``correct`` and the numbers it compared, each beside its limit.
    With ``quantize`` the lower-precision reference stands in for the
    program's tokens: the control, which has to come out not correct."""
    cell = run.cell
    wrong = [r.index for r in run.done() if len(r.out) != r.max_new]
    unfinished = len(run.recs) - len(run.done())
    picked = sample(run)
    with jax.profiler.TraceAnnotation("bench.check"):
        g = gaps(cell.family, cell.arch, params, picked,
                 cell.mix["max_len"], cell.mix["output"]["hi"], quantize)
    worst = float(max((x.max() for x in g), default=float("inf")))
    checks = {
        "max_gap": {"value": worst, "limit": limit},
        "wrong_length": {"value": len(wrong), "limit": 0},
        "unfinished": {"value": unfinished, "limit": 0},
    }
    ok = bool(picked) and worst <= limit and not wrong and not unfinished
    return {"correct": bool(ok), "checks": checks,
            "failed": len(wrong) + unfinished,
            "checked": int(sum(x.size for x in g)),
            "requests_checked": len(picked),
            "argmax": int(sum(int((x == 0).sum()) for x in g))}


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------

def device_info(dev_peak: int) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": dev_peak}


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, *,
             t_process: float, peaks: dict, log=print) -> dict:
    """Serve the cell once and return the result line's object."""
    trace_dir = Path(tempfile.mkdtemp(prefix="bench_trace_")) if traced else None
    try:
        run, params, dev_peak = serve(cell, seed, seconds, trace_dir=trace_dir,
                                      t_process=t_process, peaks=peaks)
        if traced:
            run.trace = tracemod.capture(trace_dir)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    res = check(run, params, float(cell.params["max_gap_limit"]))
    del params
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        v = load_reader(m["name"], cell.root)(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    dev = device_info(dev_peak)
    out = {"correct": res["correct"], "attempted": len(run.recs),
           "failed": res["failed"], "metrics": metrics, "device": dev}
    if traced:
        dev["busy_s"] = tracemod.busy_s(run.trace)
        dev["window_s"] = tracemod.window_s(run.trace)
        out["breakdown"] = {"device_ops": tracemod.top_ops(run.trace),
                            "idle_gaps": tracemod.idle_gaps(run.trace)}
    _log_summary(run, res, log)
    out["checks"] = res["checks"]
    return out


def _log_summary(run: Run, res: dict, log) -> None:
    cyc = run.cycles
    log(f"cell {run.cell.name} seed {run.seed}: window {run.window_s:.3f} s, "
        f"set-up {run.setup_s:.3f} s, {len(run.recs)} requests submitted, "
        f"{len(run.done())} completed, {len(cyc)} cycles, "
        f"{sum(c.prefill_steps for c in cyc)} prefill and "
        f"{sum(c.decode_steps for c in cyc)} decode steps")
    log(f"set-up phases (s): {run.notes['setup_phases_s']}; compiles in "
        f"the window: {run.notes['compiles_in_window']}; K/V at peak: "
        f"{run.notes['kv_live_peak']}")
    log(f"check: {res['checked']} served tokens of {res['requests_checked']} "
        f"requests against the reference, {res['argmax']} of them its argmax")
