"""Autotuner — sweep every tunable op x shape class, persist the winners.

PHAST's headline property is "tuning parameters without source change";
this module closes the loop by *choosing* those parameters empirically.
It enumerates every registered op from ``ops.coverage()`` (the Table-1
analogue), derives each tuning key's knob set and hand-set defaults from
the ``get_tuning`` call sites themselves
(``repro.analysis.coverage.collect_tuning_sites`` — the sweep space is
never hand-listed here), times a small deterministic candidate ladder
per serving-realistic shape case, and writes the winners to the
committed table (``tuning_table.json``, schema in ``repro.tuning.table``).

Discipline (ROADMAP standing notes):

* the backend is pinned with the *scoped* ``use_backend("pallas")`` —
  library code never calls ``set_default_backend`` (lint rule R004);
* the sweep runs under ``tuning_table({})`` so the baseline is the
  hand-set call-site defaults, not a previously committed table;
* every candidate is measured on a fresh jit (``jax.clear_caches()``
  first — tuning resolves at trace time) and its cache size is asserted
  to stay 1 across the timed repeats: a sweep value that forces retraces
  is rejected with ``RetraceRejected``, not recorded as fast;
* each shape case asserts ``registry.last_resolved(key)`` equals the
  class the driver computed — the sweep's bucketing provably matches
  the kernel call sites' bucketing;
* after the sweep, the chosen table is validated end-to-end: a tiny
  ``ServingEngine`` (attention + hybrid family) runs a mixed workload
  under ``jit_cache_audit`` with the new table loaded.

    PYTHONPATH=src python -m repro.tuning.autotune [--smoke] \
        [--ops gemm,flash_decode] [--out PATH] [--repeats N] [--no-validate]
"""
from __future__ import annotations

import argparse
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import use_backend
from repro.core.registry import (
    last_plan,
    last_resolved,
    list_ops,
    tuning_overrides,
    tuning_table,
)
from repro.tuning import table as tt
from repro.tuning.shapes import shape_class


class RetraceRejected(RuntimeError):
    """A sweep candidate forced the jit cache past size 1."""


# ---------------------------------------------------------------------------
# Shape cases: serving-realistic input builders per tuning key.
#
# Each case is (name, dims, make) where ``dims`` feeds ``shape_class``
# exactly like the kernel call site does (asserted via ``last_resolved``)
# and ``make()`` returns a zero-arg thunk running the Pallas lowering.
# Sizes are kept modest: the sweep must finish in interpret mode on CPU
# (CI) yet still separate block-size candidates.
# ---------------------------------------------------------------------------

#: (case name, shape_class dims, build) — ``build()`` returns
#: ``(pallas_thunk, ref_fn, ref_args)`` over identical inputs: the sweep
#: times the zero-arg Pallas thunk; the perf snapshot lowers
#: ``ref_fn(*ref_args)`` with the arrays as *jit arguments* for the
#: per-op roofline (closed-over arrays become HLO constants and XLA
#: folds the whole op away — and the reference HLO, not the
#: interpret-mode Pallas emulation, is the stable arithmetic footprint).
Case = Tuple[str, Dict[str, int],
             Callable[[], Tuple[Callable[[], Any],
                                Callable[..., Any], tuple]]]


def _f32(rng: np.random.Generator, *shape: int) -> jax.Array:
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


def _gemm_cases(smoke: bool) -> List[Case]:
    from repro.kernels import ref
    from repro.kernels.gemm import gemm_pallas

    shapes = [("decode_proj", 8, 256, 256)]
    if not smoke:
        shapes.append(("prefill_proj", 256, 256, 256))

    def make(m, k, n):
        def build():
            rng = np.random.default_rng(0)
            a, b = _f32(rng, m, k), _f32(rng, k, n)
            return (lambda: gemm_pallas(a, b, interpret=True),
                    ref.gemm, (a, b))
        return build

    return [(nm, dict(m=m, n=n, k=k), make(m, k, n)) for nm, m, k, n in shapes]


def _eltwise_cases(key: str) -> List[Case]:
    from repro.kernels import ref
    from repro.kernels.eltwise import bias_add_rows_pallas, relu_pallas

    m, n = 256, 512

    def build():
        rng = np.random.default_rng(0)
        x = _f32(rng, m, n)
        if key == "bias_add":
            v = _f32(rng, n)
            return (lambda: bias_add_rows_pallas(x, v, interpret=True),
                    ref.bias_add_rows, (x, v))
        return (lambda: relu_pallas(x, interpret=True),
                lambda xx: ref.relu(xx, 0.0), (x,))

    return [("rows", dict(m=m, n=n), build)]


def _conv_direct_cases() -> List[Case]:
    from repro.kernels.conv_direct import conv2d_direct_pallas

    n, c, hw, f, kk = 2, 8, 16, 64, 3

    def build():
        from repro.kernels import ref
        rng = np.random.default_rng(0)
        x = _f32(rng, n, c, hw, hw)
        w = _f32(rng, f, c, kk, kk)
        return (lambda: conv2d_direct_pallas(x, w, stride=1, pad=1,
                                             interpret=True),
                lambda xx, ww: ref.conv2d(xx, ww, None, stride=1, pad=1),
                (x, w))

    return [("conv3x3", dict(c=c, f=f), build)]


def _rmsnorm_cases() -> List[Case]:
    from repro.kernels.rmsnorm import rmsnorm_pallas

    r, d = 512, 256

    def build():
        from repro.kernels import ref
        rng = np.random.default_rng(0)
        x, w = _f32(rng, r, d), _f32(rng, d)
        return (lambda: rmsnorm_pallas(x, w, interpret=True),
                ref.rmsnorm, (x, w))

    return [("prefill_rows", dict(d=d, r=r), build)]


def _softmax_cases() -> List[Case]:
    from repro.kernels.softmax_xent import softmax_pallas

    r, v = 256, 512

    def build():
        from repro.kernels import ref
        rng = np.random.default_rng(0)
        x = _f32(rng, r, v)
        return (lambda: softmax_pallas(x, interpret=True),
                lambda xx: ref.softmax(xx, -1), (x,))

    return [("logit_rows", dict(r=r, v=v), build)]


def _softmax_xent_cases() -> List[Case]:
    from repro.kernels.softmax_xent import softmax_xent_pallas

    b, v = 256, 512

    def build():
        from repro.kernels import ref
        rng = np.random.default_rng(0)
        logits = _f32(rng, b, v)
        labels = jnp.asarray(rng.integers(0, v, b), jnp.int32)
        return (lambda: softmax_xent_pallas(logits, labels, interpret=True),
                lambda ll, yy: ref.softmax_xent(ll, yy)[0],
                (logits, labels))

    return [("train_batch", dict(b=b, v=v), build)]


def _flash_attention_cases() -> List[Case]:
    from repro.kernels.flash_attention import flash_attention_pallas

    b, s, hq, hkv, d = 1, 128, 4, 2, 64

    def build():
        from repro.kernels import ref
        rng = np.random.default_rng(0)
        q = _f32(rng, b, s, hq, d)
        k = _f32(rng, b, s, hkv, d)
        v = _f32(rng, b, s, hkv, d)
        return (lambda: flash_attention_pallas(q, k, v, causal=True,
                                               interpret=True)[0],
                lambda qq, kk_, vv: ref.mha_attention(qq, kk_, vv,
                                                      causal=True),
                (q, k, v))

    return [("train_seq", dict(d=d, s=s), build)]


def _flash_decode_cases() -> List[Case]:
    from repro.kernels.flash_attention import flash_decode_pallas

    b, smax, hq, hkv, d = 4, 512, 4, 2, 64

    def build():
        from repro.kernels.ops import _attention_decode_ref
        rng = np.random.default_rng(0)
        q = _f32(rng, b, hq, d)
        kc = _f32(rng, b, smax, hkv, d)
        vc = _f32(rng, b, smax, hkv, d)
        lens = jnp.asarray(rng.integers(smax // 2, smax, b), jnp.int32)
        return (lambda: flash_decode_pallas(q, kc, vc, lens, interpret=True),
                _attention_decode_ref, (q, kc, vc, lens))

    return [("deep_cache", dict(s=smax), build)]


def _flash_prefill_cases() -> List[Case]:
    from repro.kernels.flash_attention import flash_prefill_chunk_pallas

    b, c, smax, hq, hkv, d = 2, 32, 512, 4, 2, 64

    def build():
        from repro.kernels.ops import _attention_prefill_chunk_ref
        rng = np.random.default_rng(0)
        q = _f32(rng, b, c, hq, d)
        kc = _f32(rng, b, smax, hkv, d)
        vc = _f32(rng, b, smax, hkv, d)
        start = jnp.asarray([64, 128], jnp.int32)
        width = jnp.asarray([c, c - 5], jnp.int32)
        return (lambda: flash_prefill_chunk_pallas(q, kc, vc, start, width,
                                                   interpret=True),
                _attention_prefill_chunk_ref, (q, kc, vc, start, width))

    return [("chunked_prompt", dict(c=c, s=smax), build)]


def _quant_pool(rng: np.random.Generator, n_pages: int, page: int,
                hkv: int, d: int) -> Tuple[jax.Array, jax.Array]:
    pool = jnp.asarray(
        rng.integers(-127, 128, (n_pages, page, hkv, d)), jnp.int8
    )
    sc = jnp.asarray(rng.uniform(0.01, 0.05, (n_pages, hkv)), jnp.float32)
    return pool, sc


def _flash_decode_paged_quant_cases() -> List[Case]:
    from repro.kernels.flash_attention import flash_decode_paged_quant_pallas

    b, n_pages, page, hq, hkv, d, maxb = 4, 32, 16, 4, 2, 64, 8

    def build():
        from repro.kernels.ops import _attention_decode_paged_quant_ref
        rng = np.random.default_rng(0)
        q = _f32(rng, b, hq, d)
        kp, ksc = _quant_pool(rng, n_pages, page, hkv, d)
        vp, vsc = _quant_pool(rng, n_pages, page, hkv, d)
        lens = jnp.asarray(
            rng.integers(page * maxb // 2, page * maxb, b), jnp.int32
        )
        bt = jnp.arange(b * maxb, dtype=jnp.int32).reshape(b, maxb)
        return (lambda: flash_decode_paged_quant_pallas(
                    q, kp, vp, ksc, vsc, lens, bt, interpret=True),
                _attention_decode_paged_quant_ref,
                (q, kp, vp, ksc, vsc, lens, bt))

    return [("quant_pool", dict(p=page), build)]


def _flash_prefill_paged_quant_cases() -> List[Case]:
    from repro.kernels.flash_attention import (
        flash_prefill_chunk_paged_quant_pallas,
    )

    b, c, n_pages, page, hq, hkv, d, maxb = 2, 32, 16, 16, 4, 2, 64, 8

    def build():
        from repro.kernels.ops import (
            _attention_prefill_chunk_paged_quant_ref,
        )
        rng = np.random.default_rng(0)
        q = _f32(rng, b, c, hq, d)
        kp, ksc = _quant_pool(rng, n_pages, page, hkv, d)
        vp, vsc = _quant_pool(rng, n_pages, page, hkv, d)
        start = jnp.asarray([64, 91], jnp.int32)
        width = jnp.asarray([c, c - 5], jnp.int32)
        bt = jnp.arange(b * maxb, dtype=jnp.int32).reshape(b, maxb)
        return (lambda: flash_prefill_chunk_paged_quant_pallas(
                    q, kp, vp, ksc, vsc, start, width, bt, interpret=True),
                _attention_prefill_chunk_paged_quant_ref,
                (q, kp, vp, ksc, vsc, start, width, bt))

    return [("quant_chunked_prompt", dict(c=c, p=page), build)]


def _ssd_cases(key: str) -> List[Case]:
    from repro.kernels.mamba_scan import ssd_scan_pallas

    if key == "ssd_scan":
        b, s, h, p, n = 1, 128, 4, 32, 32
    else:
        b, s, h, p, n = 2, 64, 4, 32, 32

    def build():
        from repro.kernels import ref
        rng = np.random.default_rng(0)
        x = _f32(rng, b, s, h, p)
        dt = jnp.abs(_f32(rng, b, s, h)) * 0.1
        a = -jnp.abs(_f32(rng, h))
        bb = _f32(rng, b, s, 1, n)
        cc = _f32(rng, b, s, 1, n)
        if key == "ssd_scan":
            return (lambda: ssd_scan_pallas(x, dt, a, bb, cc,
                                            interpret=True)[0],
                    lambda *args: ref.ssd_scan(*args, chunk=64)[0],
                    (x, dt, a, bb, cc))
        state = jnp.zeros((b, h, p, n), jnp.float32)
        return (lambda: ssd_scan_pallas(
                    x, dt, a, bb, cc, initial_state=state,
                    tuning_op="ssd_prefill_chunk", interpret=True)[0],
                lambda *args: ref.ssd_scan(
                    *args[:5], chunk=64, initial_state=args[5])[0],
                (x, dt, a, bb, cc, state))

    return [("serving_seq", dict(s=s), build)]


def shape_cases(key: str, smoke: bool) -> List[Case]:
    """The shape cases swept for one tuning key."""
    if key == "gemm":
        return _gemm_cases(smoke)
    if key in ("bias_add", "relu"):
        return _eltwise_cases(key)
    if key == "conv_direct":
        return _conv_direct_cases()
    if key == "rmsnorm":
        return _rmsnorm_cases()
    if key == "softmax":
        return _softmax_cases()
    if key == "softmax_xent":
        return _softmax_xent_cases()
    if key == "flash_attention":
        return _flash_attention_cases()
    if key == "flash_decode":
        return _flash_decode_cases()
    if key == "flash_prefill":
        return _flash_prefill_cases()
    if key == "flash_decode_paged_quant":
        return _flash_decode_paged_quant_cases()
    if key == "flash_prefill_paged_quant":
        return _flash_prefill_paged_quant_cases()
    if key in ("ssd_scan", "ssd_prefill_chunk"):
        return _ssd_cases(key)
    return []


# ---------------------------------------------------------------------------
# Candidate ladder
# ---------------------------------------------------------------------------

_MIN_KNOB = 8


def candidates(
    knobs: Dict[str, Optional[int]], smoke: bool
) -> List[Dict[str, int]]:
    """Deterministic sweep points around the hand-set defaults.

    Diagonal scaling (all knobs by one factor) plus per-knob deviations
    at default others — covers the joint and marginal directions without
    a full cartesian blow-up.  The all-defaults point is the baseline and
    is excluded.
    """
    base = {k: v for k, v in knobs.items() if isinstance(v, int)}
    if not base:
        return []
    factors = (2, 1, 2) if smoke else (4, 2, 2, 4)
    # encode factors as (divisors..., multipliers...): /4 /2 x2 x4
    ndiv = 1 if smoke else 2
    scales = [1.0 / f for f in factors[:ndiv]] + [
        float(f) for f in factors[ndiv:]
    ]

    def scaled(v: int, s: float) -> int:
        return max(_MIN_KNOB, int(round(v * s)))

    out: List[Dict[str, int]] = []
    seen = {tuple(sorted(base.items()))}
    for s in scales:
        cand = {k: scaled(v, s) for k, v in base.items()}
        t = tuple(sorted(cand.items()))
        if t not in seen:
            seen.add(t)
            out.append(cand)
    if len(base) > 1:
        for k in sorted(base):
            for s in scales:
                cand = dict(base)
                cand[k] = scaled(base[k], s)
                t = tuple(sorted(cand.items()))
                if t not in seen:
                    seen.add(t)
                    out.append(cand)
    return out


# ---------------------------------------------------------------------------
# Measurement
# ---------------------------------------------------------------------------

def measure(thunk: Callable[[], Any], repeats: int) -> float:
    """Best-of-``repeats`` wall ms on a fresh jit; cache must stay size 1.

    ``jax.clear_caches()`` first: tuning resolves at trace time, so a
    stale cache would silently time the *previous* candidate's blocks.
    """
    jax.clear_caches()
    fn = jax.jit(thunk)
    jax.block_until_ready(fn())          # compile (untimed)
    if fn._cache_size() != 1:
        raise RetraceRejected(f"cache size {fn._cache_size()} after compile")
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    if fn._cache_size() != 1:
        raise RetraceRejected(
            f"candidate retraced: cache size {fn._cache_size()} after "
            f"{repeats} steady-state calls"
        )
    return 1e3 * best


def sweep_key(
    key: str,
    knobs: Dict[str, Optional[int]],
    *,
    smoke: bool,
    repeats: int,
    log: Callable[[str], None],
) -> Dict[str, Dict[str, Any]]:
    """Sweep one tuning key over its shape cases; returns table classes."""
    classes: Dict[str, Dict[str, Any]] = {}
    for case_name, dims, build in shape_cases(key, smoke):
        cls = shape_class(**dims)
        thunk = build()[0]
        default_ms = measure(thunk, repeats)
        got = last_resolved(key)
        if got != cls:
            raise AssertionError(
                f"{key}/{case_name}: driver classified {cls!r} but the "
                f"kernel call site resolved {got!r} — sweep bucketing "
                "diverged from the kernel's"
            )
        best_ms, best_params = default_ms, None
        # a kernel that plans its blocks from the call's shape (gemm) has
        # no literal defaults: sweep around the plan its baseline resolved
        base = {**knobs, **{k: v for k, v in last_plan(key).items()
                            if k in knobs}}
        for cand in candidates(base, smoke):
            with tuning_overrides(key, cls, **cand):
                try:
                    ms = measure(thunk, repeats)
                except RetraceRejected as exc:
                    log(f"    {key}[{cls}] {cand} rejected: {exc}")
                    continue
            if ms < best_ms:
                best_ms, best_params = ms, cand
        if best_params is None:
            log(f"    {key}[{cls}] ({case_name}): defaults win "
                f"({default_ms:.2f} ms)")
            continue
        classes[cls] = {
            "params": best_params,
            "ms": round(best_ms, 4),
            "default_ms": round(default_ms, 4),
            "speedup": round(default_ms / best_ms, 3),
            "case": case_name,
        }
        log(f"    {key}[{cls}] ({case_name}): {best_params} "
            f"{default_ms:.2f} -> {best_ms:.2f} ms "
            f"(x{default_ms / best_ms:.2f})")
    return classes


# ---------------------------------------------------------------------------
# Orchestration
# ---------------------------------------------------------------------------

def enumerate_cells(
    only: Optional[Sequence[str]] = None,
) -> List[Dict[str, Any]]:
    """One audit cell per registered op, in deterministic (sorted) order."""
    import repro.kernels.ops  # noqa: F401  - populates the registry

    cells: List[Dict[str, Any]] = []
    for name, entry in sorted(list_ops().items()):
        keys = sorted(entry.tuning or ())
        if entry.pallas is None:
            status = "reference_only"
        elif not keys:
            status = "no-knobs"
        elif only is not None and not any(k in only for k in keys):
            status = "skipped"
        else:
            status = "swept"
        cells.append({"op": name, "status": status, "keys": keys})
    return cells


def run_autotune(
    *,
    smoke: bool = False,
    only: Optional[Sequence[str]] = None,
    repeats: int = 3,
    log: Callable[[str], None] = lambda s: None,
) -> Dict[str, Any]:
    """Full sweep; returns a validated table document (not yet saved)."""
    from repro.analysis.coverage import collect_tuning_sites

    sites = collect_tuning_sites()
    cells = enumerate_cells(only)
    key_ops: Dict[str, List[str]] = {}
    for c in cells:
        for k in c["keys"]:
            key_ops.setdefault(k, []).append(c["op"])

    doc = tt.empty_doc()
    doc["environment"] = {
        "jax": jax.__version__,
        "device": jax.devices()[0].platform,
        "interpret": True,
        "smoke": bool(smoke),
        "repeats": int(repeats),
    }
    doc["cells"] = cells

    sweep_keys = sorted(
        k for c in cells if c["status"] == "swept" for k in c["keys"]
    )
    sweep_keys = sorted(set(sweep_keys))
    # pin the backend in scope (R004) and sweep against a clean slate so
    # the baseline is the hand-set call-site defaults
    with use_backend("pallas"), tuning_table(None):
        for key in sweep_keys:
            knobs = sites.get(key, {})
            if not knobs:
                log(f"  {key}: no derivable knobs, skipped")
                continue
            log(f"  {key}: knobs {knobs}")
            classes = sweep_key(key, knobs, smoke=smoke, repeats=repeats,
                                log=log)
            if classes:
                for cell in classes.values():
                    cell["ops"] = key_ops.get(key, [])
                doc["entries"][key] = classes

    errors = tt.validate(doc)
    if errors:
        raise RuntimeError("autotune produced an invalid table: "
                           + "; ".join(errors))
    return doc


def validate_serving(doc: Dict[str, Any], log: Callable[[str], None]) -> None:
    """Prove the swept table serves cleanly: tiny engines, audited jit.

    Runs a mixed prefill/decode workload on an attention arch and the
    hybrid (attention+SSD) arch with the new table loaded; any retrace
    caused by a table value raises ``JitCacheRetrace``.
    """
    from repro.analysis.audit import jit_cache_audit
    from repro.configs.registry import get_arch
    from repro.models.model import build_model

    for arch in ("qwen2.5-3b-smoke", "zamba2-2.7b-smoke"):
        cfg = get_arch(arch)
        model = build_model(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        rng = np.random.default_rng(7)
        from repro.serving.engine import ServingEngine

        with use_backend("pallas"), tuning_table(doc):
            eng = ServingEngine(model, params, batch=2, max_len=32,
                                steps_per_sync=4, prefill_chunk=4)
            with jit_cache_audit(eng):
                for _ in range(3):
                    toks = rng.integers(
                        0, cfg.vocab_size, rng.integers(3, 9)
                    ).tolist()
                    eng.submit(toks, 4)
                outs = eng.run()
        assert len(outs) == 3
        log(f"  serving validation ok: {arch}")


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.tuning.autotune",
        description="sweep tunable ops and persist tuning_table.json",
    )
    ap.add_argument("--smoke", action="store_true",
                    help="fewer shape cases/candidates (CI round-trip test)")
    ap.add_argument("--ops", default=None,
                    help="comma-separated tuning keys to sweep (default all)")
    ap.add_argument("--out", default=None,
                    help="output path (default: the committed table)")
    ap.add_argument("--repeats", type=int, default=None,
                    help="timed repeats per candidate (default 3; smoke 1)")
    ap.add_argument("--no-validate", action="store_true",
                    help="skip the post-sweep serving validation")
    args = ap.parse_args(argv)

    only = args.ops.split(",") if args.ops else None
    repeats = args.repeats if args.repeats is not None else (
        1 if args.smoke else 3
    )

    def log(s: str) -> None:
        print(s, flush=True)

    log(f"autotune: smoke={args.smoke} repeats={repeats} "
        f"keys={only or 'all'}")
    doc = run_autotune(smoke=args.smoke, only=only, repeats=repeats, log=log)
    if not args.no_validate:
        validate_serving(doc, log)
    path = tt.save(doc, args.out)
    n = sum(len(v) for v in doc["entries"].values())
    log(f"wrote {n} entries ({len(doc['entries'])} keys) -> {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
