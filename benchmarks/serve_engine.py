"""Serving-loop benchmark — host-side scheduler vs device-side engine.

The paper's §4.3 finding is that a partial port pays for every crossing
between the ported domain and the host orchestrator.  The two serving
loops here are that ablation, applied to continuous batching:

    host-loop   per-row Python scheduling: an ``int()`` host sync per row
                per decode step to pick prompt-vs-generated feeding and to
                test completion (the pre-engine ``examples/serve_batched``)
    engine      ``repro.serving.ServingEngine``: control state on-device,
                one fused jit per batch of steps, one host sync per cycle

Same model, same requests, greedy decode; reported number is generated
tokens per second.

A second ablation compares the two KV-cache layouts on an attention arch
at mixed prompt lengths (paged pool capped at half the contiguous slab):
gen tok/s and peak resident KV bytes, outputs token-identical.

A third ablation measures *prompt ingestion*: chunked prefill
(``--prefill-chunk`` tokens per step) vs token-by-token, long prompts
under both layouts — prefill tok/s and mean TTFT, outputs token-identical
across all four engines.

A fourth ablation measures *prefix sharing*: N requests carrying the same
long system prompt, with and without page-level prefix sharing/CoW —
sharer TTFT and peak resident KV bytes, outputs token-identical.

``--faults`` adds a *pressure* cell (robustness harness, not a perf
table): the same submission sequence served unpressured and under an
injected ``FaultPlan`` — a pool-exhaustion window that forces a
preemption (host spill) and delays the restore, a cancel, and a
deadline storm.  The pressured engine must drain the doomed requests
through the release path, finish the survivors with *bit-identical*
tokens, and hand back every page and snapshot slot in both tiers.

``--layout`` scopes the single-layout sections to one KV layout so a CI
matrix cell (backend x layout) exercises exactly its own path; the
inherently cross-layout ablation only runs under the default ``both``.

    PYTHONPATH=src python -m benchmarks.serve_engine [--quick]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.audit import jit_cache_audit
from repro.configs.registry import get_arch
from repro.models.model import build_model
from repro.serving import (
    CacheConfig,
    EngineConfig,
    FaultEvent,
    FaultPlan,
    ServingEngine,
    SpecConfig,
)


def _audit_ctx(eng, enabled):
    """Under --audit, fail loudly on any retrace instead of timing it."""
    import contextlib

    return jit_cache_audit(eng) if enabled else contextlib.nullcontext()


def _kv_dtype(args, layout):
    """Paged cells inherit ``--kv-dtype``; the contiguous slab is never
    quantized (CacheConfig enforces the same rule)."""
    return args.kv_dtype if layout == "paged" else "f32"


def _quant_note(section):
    """Cross-grain token-identity asserts are waived under int8 pools.

    Per-page scales make the quantization grain part of the write path: a
    chunked write quantizes a whole page rung against one amax, while a
    token-by-token write max-merges and requantizes — dequantized content
    can differ by a fraction of a quantization step, which is enough to
    flip greedy near-ties.  Cells that share one write grain (host vs
    engine at chunk 1, pressured vs unpressured) still assert exact
    identity; int8-vs-f32 parity is asserted at controlled horizons in
    tests/test_kv_quant.py."""
    print(f"  (token-identity assert waived under kv_dtype=int8: "
          f"{section} changes the quantization grain)")


def make_requests(seed, n, vocab_size, gen, lo=4, hi=12):
    rng = np.random.default_rng(seed)
    return [
        (rng.integers(0, vocab_size, size=int(rng.integers(lo, hi))).tolist(),
         gen)
        for _ in range(n)
    ]


def run_host_loop(model, params, reqs, batch, max_len, cache=None):
    """The pre-engine loop: per-row Python control with host syncs.

    One fix over the seed example is kept so the comparison is between two
    *correct* schedulers: admitted rows get their decode caches reset (the
    seed leaked the previous request's SSM state into its replacement).

    ``cache`` mirrors the engine's CacheConfig: under ``--kv-dtype int8``
    the engine-vs-host token assert is only exact when both loops write
    the same quantized pool token-by-token (same quantization grain)."""
    queue = [jnp.asarray(t, jnp.int32) for t, _ in reqs]
    gens = [g for _, g in reqs]
    decode = jax.jit(model.decode_step, donate_argnums=(1,))
    reset = jax.jit(model.reset_decode_rows, donate_argnums=(0,))
    # compile outside the timed region (a server compiles once at startup)
    wstate = model.init_decode_state(batch, max_len, per_row_pos=True,
                                     cache=cache)
    wstate = reset(wstate, jnp.zeros((batch,), bool))
    logits, wstate = decode(params, wstate, jnp.zeros((batch,), jnp.int32))
    jax.block_until_ready(logits)
    state = model.init_decode_state(batch, max_len, per_row_pos=True,
                                    cache=cache)
    slots = [None] * batch
    progress = [0] * batch
    outputs = {}
    done = 0
    next_req = 0
    t0 = time.perf_counter()
    steps = 0
    while done < len(reqs):
        admit = np.zeros((batch,), bool)
        for b in range(batch):
            if slots[b] is None and next_req < len(reqs):
                slots[b] = next_req
                progress[b] = 0
                outputs[next_req] = []
                next_req += 1
                admit[b] = True
        if admit.any():
            state = reset(state, jnp.asarray(admit))
        toks = []
        for b in range(batch):
            r = slots[b]
            if r is None:
                toks.append(0)
            elif progress[b] < len(queue[r]):
                toks.append(int(queue[r][progress[b]]))   # host sync per row
            else:
                toks.append(int(outputs[r][-1]))          # host sync per row
        logits, state = decode(params, state, jnp.asarray(toks, jnp.int32))
        steps += 1
        nxt = jnp.argmax(logits, axis=-1)
        for b in range(batch):
            r = slots[b]
            if r is None:
                continue
            progress[b] += 1
            if progress[b] >= len(queue[r]):
                outputs[r].append(int(nxt[b]))            # host sync per row
                if len(outputs[r]) >= gens[r]:
                    done += 1
                    slots[b] = None
    dt = time.perf_counter() - t0
    total_gen = sum(gens)
    return {"tok_s": total_gen / dt, "steps": steps, "seconds": dt,
            "outputs": outputs}


def run_engine(model, params, reqs, batch, max_len, steps_per_sync,
               audit=False, cache=None, config=None):
    if config is None:
        config = EngineConfig(steps_per_sync=steps_per_sync)
    eng = ServingEngine(model, params, batch=batch, max_len=max_len,
                        cache=cache, config=config)
    with _audit_ctx(eng, audit):
        # compile outside the timed region (a server compiles once at
        # startup): a throwaway workload drives admit + fused-step
        # (+ prefill) traces once
        for _ in range(batch):
            eng.submit([1, 2, 3], 2)
        eng.run()
        eng.reset_stats()

        rids = [eng.submit(t, g) for t, g in reqs]
        t0 = time.perf_counter()
        outs = eng.run()
        dt = time.perf_counter() - t0
    ttft = [eng.ttft[r] for r in rids if r in eng.ttft]
    row = {"tok_s": eng.generated / dt, "steps": eng.steps, "seconds": dt,
           "prefill_steps": eng.prefill_steps,
           "prefill_tok_s": eng.prompt_tokens / dt,
           "ttft_ms": 1e3 * float(np.mean(ttft)) if ttft else float("nan"),
           "ttft_ms_p99": (1e3 * float(np.percentile(ttft, 99))
                           if ttft else float("nan")),
           "kv_bytes": eng.kv_resident_bytes(peak=True),
           "outputs": {i: outs[r].tolist() for i, r in enumerate(rids)}}
    if eng.spec is not None:
        st = eng.stats()
        row.update(
            spec_accepted=int(st["spec_accepted"]),
            spec_proposed=int(st["spec_proposed"]),
            spec_emitted=int(st["spec_emitted"]),
            spec_accept_rate=float(st["spec_accept_rate"]),
        )
    return row


def compare_layouts(args):
    """Paged vs contiguous at mixed prompt lengths (the memory ablation).

    Prompt lengths span >= 8x, so the contiguous slab (B x max_len per
    row, sized for the *longest* request) is mostly idle padding.  The
    paged engine's pool is capped at half the slab; throughput must hold
    while peak resident KV drops to roughly the live-token footprint."""
    cfg = get_arch(args.kv_arch)
    if cfg.is_attention_free:
        print("  (skipped: attention-free arch — no KV cache to page; "
              "recurrent state is O(1) per row under either layout)")
        return {}
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    lo, hi = 4, 33                            # >= 8x spread
    max_len = hi + args.gen + 1
    reqs = make_requests(1, args.requests, cfg.vocab_size, args.gen,
                         lo=lo, hi=hi)
    page = args.page_size
    # pool deliberately below the contiguous equivalent (half the slab),
    # but never below the largest single request's worst-case need — a
    # request bigger than the whole pool is rejected at submit
    from repro.serving.pager import pages_needed
    full_pool = args.batch * (-(-max_len // page))
    max_need = max(pages_needed(len(t) + g, page) for t, g in reqs)
    quant = args.kv_dtype != "f32"
    pool = max(max_need, full_pool // 2)
    cells = [
        ("contiguous", CacheConfig()),
        ("paged", CacheConfig(layout="paged", page_size=page, n_pages=pool,
                              kv_dtype=args.kv_dtype)),
    ]
    if quant:
        # the f32 twin of the quantized pool: token-identity baseline for
        # the contiguous compare and denominator of the exact-2x byte check
        cells.append(("paged_f32", CacheConfig(layout="paged",
                                               page_size=page,
                                               n_pages=pool)))
    rows = {}
    for name, cache in cells:
        rows[name] = run_engine(model, params, reqs, args.batch, max_len,
                                args.steps_per_sync, audit=args.audit,
                                cache=cache)
    ident = "paged_f32" if quant else "paged"
    for i in range(len(reqs)):
        a, b = rows["contiguous"]["outputs"][i], rows[ident]["outputs"][i]
        assert a == b, f"request {i}: contiguous {a} != {ident} {b}"
    ratio = {"f32": 1, "bf16": 2, "int8": 4}[args.kv_dtype]
    if quant:
        # the packed payload must land exactly on the itemsize ladder —
        # bf16 = 1/2 the f32 pool, int8 = 1/4 (per-page scales ride in a
        # side pool the byte counter deliberately excludes)
        assert rows["paged"]["kv_bytes"] * ratio == rows["paged_f32"]["kv_bytes"], (
            f"{args.kv_dtype} pool not exactly 1/{ratio} the f32 pool: "
            f"{rows['paged']['kv_bytes']} vs {rows['paged_f32']['kv_bytes']}"
        )
    print(f"arch={args.kv_arch} requests={args.requests} batch={args.batch} "
          f"gen={args.gen} prompt_len {lo}..{hi - 1} page_size={page} "
          f"kv_dtype={args.kv_dtype}")
    print(f"  {'layout':<12} {'gen tok/s':>10} {'peak KV bytes':>14} "
          f"{'vs slab':>8}")
    slab = rows["contiguous"]["kv_bytes"]
    for name, _ in cells:
        r = rows[name]
        print(f"  {name:<12} {r['tok_s']:>10.1f} {r['kv_bytes']:>14d} "
              f"{r['kv_bytes'] / slab:>7.0%}")
    if quant:
        print(f"  (contiguous vs paged_f32 token-identical; {args.kv_dtype} "
              f"resident KV exactly 1/{ratio} the f32 pool)")
    else:
        print("  (outputs token-identical)")
    return rows


def compare_prefix_sharing(args):
    """Prefix sharing on/off under the shared-system-prompt workload (the
    resident-memory + TTFT ablation).

    N requests share a long page-aligned prompt prefix (a system prompt)
    plus a short unique tail.  The donor is admitted alone and ingests the
    full prefix; the other N-1 arrive while it is still decoding — the
    exact schedule vLLM-style prefix caching exists for.  Sharing must
    leave every token identical while the sharers' TTFT and the peak
    resident KV bytes collapse (each shared page is resident once, not
    once per row)."""
    import dataclasses

    cfg = get_arch(args.kv_arch)
    if args.share_requests < 2:
        print("  (skipped: --share-requests < 2 — sharing needs a donor "
              "and at least one sharer)")
        return {}
    if args.prefill_vocab:
        cfg = dataclasses.replace(cfg, vocab_size=args.prefill_vocab)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    n = args.share_requests
    plen = args.share_prefix_len
    rng = np.random.default_rng(7)
    prefix = rng.integers(0, cfg.vocab_size, size=plen).tolist()
    tails = [rng.integers(0, cfg.vocab_size, size=4).tolist()
             for _ in range(n)]
    if plen % args.page_size == 0 and n > 1:
        # one fully shared prompt: its re-fed last token exercises CoW
        tails[-1] = []
    gen = args.prefill_gen
    # the donor must outlive the sharers' admission (one sync cycle later)
    donor_gen = gen + args.steps_per_sync + 1
    max_len = plen + 4 + donor_gen + 1

    def run(sharing):
        eng = ServingEngine(
            model, params, batch=n, max_len=max_len,
            cache=CacheConfig(layout="paged", page_size=args.page_size,
                              kv_dtype=args.kv_dtype),
            config=EngineConfig(
                steps_per_sync=args.steps_per_sync,
                prefill_chunk=args.prefill_chunk, prefix_sharing=sharing,
            ),
        )
        with _audit_ctx(eng, args.audit):
            for _ in range(2):                 # compile outside the clock
                eng.submit([1, 2, 3], 2)
            eng.run()
            eng.reset_stats()
            rid0 = eng.submit(prefix + tails[0], donor_gen)
            eng.step()                         # donor ingests the prefix
            rids = [rid0] + [
                eng.submit(prefix + t, gen) for t in tails[1:]
            ]
            pt0 = eng.prompt_tokens            # donor's pre-window tokens
            t0 = time.perf_counter()
            outs = eng.run()
            dt = time.perf_counter() - t0
        ttft = [eng.ttft[r] for r in rids[1:] if r in eng.ttft]
        return {
            "ttft_ms": 1e3 * float(np.mean(ttft)) if ttft else float("nan"),
            "prefill_tok_s": (eng.prompt_tokens - pt0) / dt,
            "kv_bytes": eng.kv_resident_bytes(peak=True),
            "shared": eng.shared_prompt_tokens,
            "cow": eng.cow_pages,
            "outputs": {i: outs[r].tolist() for i, r in enumerate(rids)},
        }

    rows = {name: run(s) for name, s in (("unshared", False),
                                         ("shared", True))}
    if args.kv_dtype != "int8":
        # holds for bf16 too: the rounding is element-wise, so shared and
        # unshared pools store bitwise-identical prefix pages
        assert rows["shared"]["outputs"] == rows["unshared"]["outputs"], (
            "prefix sharing changed tokens"
        )
    else:
        # a sharer resumes mid-page: its boundary page mixes donor-grain
        # prefix slots with tail rungs the unshared run quantized together
        _quant_note("prefix sharing")
    assert rows["shared"]["shared"] > 0, "sharing never engaged"
    print(f"arch={args.kv_arch} [{cfg.family}] requests={n} "
          f"prefix_len={plen} tail=4 gen={gen} page_size={args.page_size} "
          f"chunk={args.prefill_chunk}")
    print(f"  {'sharing':<10} {'sharer TTFT ms':>14} {'prefill tok/s':>13} "
          f"{'peak KV bytes':>14} {'shared toks':>11} {'CoW':>4}")
    for name in ("unshared", "shared"):
        r = rows[name]
        print(f"  {name:<10} {r['ttft_ms']:>14.1f} "
              f"{r['prefill_tok_s']:>13.1f} {r['kv_bytes']:>14d} "
              f"{r['shared']:>11d} {r['cow']:>4d}")
    ttft_x = rows["unshared"]["ttft_ms"] / rows["shared"]["ttft_ms"]
    msg = f"  TTFT {ttft_x:.1f}x"
    if rows["shared"]["kv_bytes"]:   # attention-free archs have no KV pages
        drop = rows["unshared"]["kv_bytes"] / rows["shared"]["kv_bytes"]
        msg = f"  resident-KV drop {drop:.1f}x," + msg[1:]
    ident = ("outputs token-identical" if args.kv_dtype != "int8"
             else "identity waived under int8")
    print(msg + f" ({ident})")
    return rows


def compare_prefill(args):
    """Chunked vs token-by-token prompt ingestion (the TTFT ablation).

    Long prompts, short generations: the workload the chunked-prefill path
    exists for.  Four engines — chunk 1 and chunk C under each KV layout —
    serve the same requests; outputs must be token-identical everywhere,
    and the table reports prompt-ingestion tok/s plus mean TTFT so the
    ``ceil(P/C)``-steps win is visible as wall-clock, not step counts.

    The smoke archs carry a toy 128-entry vocab, which erases the LM-head
    GEMM a real server pays on *every* token-by-token prompt step (the
    chunked path computes logits once per chunk).  ``--prefill-vocab``
    restores a serving-scale vocabulary for this ablation so the baseline
    is the workload the optimization targets."""
    import dataclasses

    cfg = get_arch(args.kv_arch)
    if args.prefill_vocab:
        cfg = dataclasses.replace(cfg, vocab_size=args.prefill_vocab)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    plen = args.prompt_len
    max_len = plen + args.prefill_gen + 1
    rng = np.random.default_rng(3)
    reqs = [
        (rng.integers(0, cfg.vocab_size, size=plen).tolist(),
         args.prefill_gen)
        for _ in range(args.prefill_requests)
    ]
    chunks = sorted({1, args.prefill_chunk})    # chunk 1 = the baseline
    layouts = (("contiguous", "paged") if args.layout == "both"
               else (args.layout,))
    rows = {}
    for layout in layouts:
        cache = CacheConfig(layout=layout, page_size=args.page_size,
                            kv_dtype=_kv_dtype(args, layout))
        for pc in chunks:
            rows[(layout, pc)] = run_engine(
                model, params, reqs, args.batch, max_len,
                args.steps_per_sync, audit=args.audit, cache=cache,
                config=EngineConfig(steps_per_sync=args.steps_per_sync,
                                    prefill_chunk=pc),
            )
    if args.kv_dtype != "int8":
        # bf16 included: chunked and token-by-token writes round the same
        # values element-wise, so every chunk width stores the same pool
        base = rows[(layouts[0], 1)]["outputs"]
        for key, r in rows.items():
            assert r["outputs"] == base, (
                f"{key}: outputs diverge from baseline"
            )
    else:
        _quant_note("chunk width")
    print(f"arch={args.kv_arch} requests={args.prefill_requests} "
          f"batch={args.batch} prompt_len={plen} gen={args.prefill_gen} "
          f"chunk={args.prefill_chunk}")
    print(f"  {'layout':<12} {'chunk':>5} {'prefill tok/s':>13} "
          f"{'mean TTFT ms':>12} {'gen tok/s':>10} {'steps':>6} {'pf':>4}")
    for (layout, pc), r in rows.items():
        print(f"  {layout:<12} {pc:>5d} {r['prefill_tok_s']:>13.1f} "
              f"{r['ttft_ms']:>12.1f} {r['tok_s']:>10.1f} "
              f"{r['steps']:>6d} {r['prefill_steps']:>4d}")
    if args.prefill_chunk > 1:
        ident = ("outputs token-identical" if args.kv_dtype != "int8"
                 else "identity waived under int8")
        for layout in layouts:
            speedup = (rows[(layout, args.prefill_chunk)]["prefill_tok_s"]
                       / rows[(layout, 1)]["prefill_tok_s"])
            print(f"  {layout}: prompt-ingestion speedup "
                  f"{speedup:.2f}x ({ident})")
    return rows


def _lookup_score(seq, plen, ngram):
    """Fraction of generated positions a prompt-lookup drafter would have
    predicted: the continuation after the most recent earlier match of the
    trailing n-gram equals the actual next token."""
    hits = total = 0
    for t in range(max(plen, ngram), len(seq)):
        key = tuple(seq[t - ngram:t])
        pred = None
        for s in range(t - ngram - 1, -1, -1):
            if tuple(seq[s:s + ngram]) == key:
                pred = seq[s + ngram]
                break
        total += 1
        hits += pred == seq[t]
    return hits / max(total, 1)


def run_spec(args):
    """Speculative decoding: drafted tokens through the chunked verifier
    vs plain decode (the accept-rate / latency ablation).

    The workload is the one prompt lookup exists for — generations that
    repeat their own context (the summarization / code-copy regime).  A
    randomly-initialised smoke model only settles into an n-gram-
    predictable greedy cycle on a fraction of prompts, so a pre-pass
    decodes 20x candidate repeated-suffix prompts once and keeps the rows
    whose continuation a lookup drafter would actually predict — selecting
    the target regime rather than hoping random weights land in it.

    Each K runs against a shared K=0 baseline per layout; outputs must be
    token-identical at every K (greedy acceptance emits only verifier-
    argmax tokens, so speculation is a pure latency move), at least one
    draft must be accepted, and at benchmark scale (gen >= 16) the best
    prompt-lookup K must clear 1.3x the baseline's gen tok/s."""
    cfg = get_arch(args.kv_arch)
    ks = [int(k) for k in str(args.spec_k).split(",") if k.strip()]
    ks = sorted({k for k in ks if k > 0})
    if not ks:
        print("  (skipped: --spec-k 0 — no draft widths requested)")
        return {}
    if args.spec_drafter == "hybrid_ssm" and cfg.family != "hybrid":
        print("  (skipped: drafter='hybrid_ssm' drafts with the hybrid "
              "family's own Mamba layers — pick --family hybrid)")
        return {}
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    gen = args.gen
    rng = np.random.default_rng(11)
    cands = []
    for _ in range(20 * args.requests):
        motif = rng.integers(0, cfg.vocab_size, size=3).tolist()
        head = rng.integers(0, cfg.vocab_size, size=2).tolist()
        cands.append((head + motif * 4, gen))    # repeated suffix
    max_len = max(len(t) for t, _ in cands) + gen + 1
    pc = max(2, args.prefill_chunk)              # spec needs the chunked path
    probe = run_engine(model, params, cands, args.batch, max_len,
                       args.steps_per_sync, audit=args.audit,
                       config=EngineConfig(steps_per_sync=args.steps_per_sync,
                                           prefill_chunk=pc))
    scores = [
        _lookup_score(cands[i][0] + probe["outputs"][i], len(cands[i][0]),
                      args.spec_ngram)
        for i in range(len(cands))
    ]
    ranked = sorted(range(len(cands)), key=lambda i: -scores[i])
    reqs = [cands[i] for i in sorted(ranked[:args.requests])]
    # a wandering model (possible at smoke scale: 6-token continuations
    # from random weights) can leave nothing predictable to accept — the
    # cell still checks token identity, but accepted>0 is only a
    # meaningful invariant when the pre-pass found predictable rows
    predictable = scores[ranked[0]] >= 0.3
    layouts = (("contiguous", "paged") if args.layout == "both"
               else (args.layout,))
    rows = {}
    for layout in layouts:
        cache = CacheConfig(layout=layout, page_size=args.page_size,
                            kv_dtype=_kv_dtype(args, layout))
        for k in [0] + ks:
            spec = (SpecConfig(k=k, drafter=args.spec_drafter,
                               ngram=args.spec_ngram) if k else None)
            rows[(layout, f"k{k}")] = run_engine(
                model, params, reqs, args.batch, max_len,
                args.steps_per_sync, audit=args.audit, cache=cache,
                config=EngineConfig(steps_per_sync=args.steps_per_sync,
                                    prefill_chunk=pc, spec=spec),
            )
    quant_paged = args.kv_dtype == "int8" and "paged" in layouts
    for (layout, kk), r in rows.items():
        if _kv_dtype(args, layout) != "int8":
            base = rows[(layout, "k0")]["outputs"]
            assert r["outputs"] == base, (
                f"{layout} {kk}: speculative outputs diverge from plain "
                f"decode"
            )
            if kk != "k0" and predictable:
                assert r["spec_accepted"] > 0, (
                    f"{layout} {kk}: no draft was ever accepted"
                )
    if quant_paged:
        # rejected drafts max-merge into page scales before the rewind, and
        # scales never shrink — the verifier reads a slightly coarser page
        # than plain decode ever wrote
        _quant_note("draft-write rewind")
    print(f"arch={args.kv_arch} [{cfg.family}] requests={args.requests} "
          f"batch={args.batch} gen={gen} drafter={args.spec_drafter} "
          f"ngram={args.spec_ngram} chunk={pc}")
    print(f"  {'layout':<12} {'K':>3} {'gen tok/s':>10} {'accept':>7} "
          f"{'emitted':>8} {'vs K=0':>7}")
    for layout in layouts:
        base = rows[(layout, "k0")]["tok_s"]
        for k in [0] + ks:
            r = rows[(layout, f"k{k}")]
            acc = f"{r['spec_accept_rate']:.0%}" if k else "-"
            print(f"  {layout:<12} {k:>3d} {r['tok_s']:>10.1f} {acc:>7} "
                  f"{r.get('spec_emitted', 0):>8d} "
                  f"{r['tok_s'] / base:>6.2f}x")
    if not predictable:
        print("  (pre-pass found no lookup-predictable continuations at "
              "this scale — accept-rate floor waived, identity still held)")
    ident = ("outputs token-identical" if not quant_paged
             else "f32 cells token-identical")
    if gen >= 16 and predictable and args.spec_drafter == "prompt_lookup":
        for layout in layouts:
            base = rows[(layout, "k0")]["tok_s"]
            best = max(rows[(layout, f"k{k}")]["tok_s"] for k in ks)
            assert best >= 1.3 * base, (
                f"{layout}: best speculative tok/s {best:.1f} < 1.3x the "
                f"plain-decode baseline {base:.1f} on the repeated-suffix "
                "cell"
            )
        print(f"  (speculation >= 1.3x plain decode per layout; {ident})")
    else:
        print(f"  ({ident} across K)")
    return rows


def _assert_conserved(eng, label):
    """Post-drain invariant: every pool the engine owns — device and host,
    KV pages and snapshot slots — fully free, every table clear.  Zero
    leaked pages/slots is the acceptance bar for the pressure cell."""
    st = eng._mstate
    for top, free, table in (
        ("page_top", "page_free", "block_table"),
        ("host_top", "host_free", "host_table"),
        ("snap_top", "snap_free", "snap_table"),
        ("hsnap_top", "hsnap_free", "hsnap_table"),
    ):
        if top not in st:
            continue
        nslots = st[free].shape[0]
        leaked = nslots - int(st[top])
        assert leaked == 0, f"{label}: {top} leaked {leaked}/{nslots} slots"
        assert bool((np.asarray(st[table]) == -1).all()), (
            f"{label}: {table} still maps freed rows"
        )


def _pressure_cell(args, layout):
    """One --faults cell: unpressured baseline vs FaultPlan-injected run.

    Paged: pool sized so the high-priority arrival *must* preempt the
    resident low-priority long request mid-prefill (host spill), and an
    exhaustion window provably delays its restore.  Contiguous (no pool
    to squeeze): the cancel/deadline half of the plan only.  Either way
    the survivors' tokens must be bit-identical to the baseline's and no
    page or snapshot slot may leak."""
    cfg = get_arch(args.kv_arch)
    spillable = layout == "paged" and not cfg.is_attention_free
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    gen_long, gen_short = 8, 6
    rng = np.random.default_rng(17)
    long_prompt = rng.integers(0, cfg.vocab_size, size=24).tolist()
    shorts = [rng.integers(0, cfg.vocab_size, size=6).tolist()
              for _ in range(3)]

    def mk(n_pages=None, budget=0):
        paged = layout == "paged"
        # survivor bit-identity survives int8: baseline and pressured runs
        # share one chunk decomposition per row, and spill/restore moves
        # quantized payload and scales byte-exactly
        cache = CacheConfig(
            layout=layout, page_size=4 if paged else 16,
            n_pages=n_pages if paged else None,
            kv_dtype=_kv_dtype(args, layout),
        )
        return ServingEngine(
            model, params, batch=2, max_len=40, cache=cache,
            config=EngineConfig(
                steps_per_sync=2, prefill_chunk=4, prefill_budget=budget,
                prefix_sharing=paged and not cfg.is_attention_free,
            ),
        )

    def drive(eng, plan=None):
        with _audit_ctx(eng, args.audit):
            rids = [eng.submit(long_prompt, gen_long, priority=0)]
            eng.step()              # low-priority long request resident
            rids.append(eng.submit(shorts[0], gen_short, priority=1))
            rids.append(eng.submit(shorts[1], gen_short, priority=0))
            rids.append(eng.submit(shorts[2], gen_short, priority=0,
                                   deadline_ms=60_000.0))
            if plan is not None:
                eng.set_fault_plan(plan(rids))
            outs = eng.run()
        return rids, outs

    base_rids, base_outs = drive(mk(n_pages=20))

    def plan(rids):
        events = [
            FaultEvent(cycle=2, kind="cancel", req_id=rids[2]),
            FaultEvent(cycle=2, kind="deadline", req_id=rids[3],
                       deadline_ms=0.0),
        ]
        if spillable:
            events += [
                FaultEvent(cycle=1, kind="exhaust_pool", pages=4),
                FaultEvent(cycle=8, kind="release_pool"),
            ]
        return FaultPlan(events=tuple(events))

    # paged: pool == the long request's worst-case need, so the
    # high-priority arrival cannot fit without spilling it
    eng = mk(n_pages=8, budget=1)
    rids, outs = drive(eng, plan)

    survivors = [rids[0], rids[1]]
    assert sorted(outs) == sorted(survivors), (
        f"{layout}: expected only survivors {survivors}, got {sorted(outs)}"
    )
    for r in survivors:
        assert np.array_equal(outs[r], base_outs[r]), (
            f"{layout}: survivor {r} diverged from unpressured run"
        )
    assert rids[2] in eng.cancelled, f"{layout}: cancel never landed"
    assert rids[3] in eng.expired, f"{layout}: deadline never landed"
    if spillable:
        assert eng.preemptions >= 1, "pressure never forced a preemption"
        assert eng.restores >= 1, "spilled row was never restored"
    _assert_conserved(eng, layout)

    row = {"preemptions": eng.preemptions, "restores": eng.restores,
           "cancelled": len(eng.cancelled), "expired": len(eng.expired),
           "survivors": len(outs)}
    print(f"  {layout:<12} {row['preemptions']:>8d} {row['restores']:>8d} "
          f"{row['cancelled']:>9d} {row['expired']:>7d} "
          f"{row['survivors']:>9d}   ok")
    return row


def run_pressure(args):
    """The --faults section: serving survives injected pressure."""
    layouts = (("contiguous", "paged") if args.layout == "both"
               else (args.layout,))
    print(f"arch={args.kv_arch} batch=2 prompt_len=24/6 gen=8/6 "
          f"prefill_chunk=4 prefill_budget=1")
    print(f"  {'layout':<12} {'preempt':>8} {'restore':>8} "
          f"{'cancelled':>9} {'expired':>7} {'survivors':>9}")
    out = {}
    for layout in layouts:
        if layout == "paged" and get_arch(args.kv_arch).is_attention_free:
            print("  (paged cell skipped: attention-free arch — no KV "
                  "pages to spill)")
            continue
        out[layout] = _pressure_cell(args, layout)
    print("  (survivor outputs bit-identical to unpressured run; all "
          "pools conserved)")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-2.7b-smoke")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--steps-per-sync", type=int, default=8)
    ap.add_argument("--kv-arch", default="qwen2.5-3b-smoke",
                    help="attention arch for the paged-vs-contiguous ablation")
    ap.add_argument("--family", choices=["dense", "moe", "ssm", "hybrid"],
                    default=None,
                    help="pick the prefill/sharing-ablation arch by family "
                         "(overrides --kv-arch with that family's smoke "
                         "config) — the recurrent cells exercise chunked "
                         "SSD prefill and snapshot-restore sharing")
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=64,
                    help="prompt tokens per chunked-prefill step in the "
                         "prefill ablation (1 disables chunking)")
    ap.add_argument("--prompt-len", type=int, default=256,
                    help="prompt length for the prefill ablation")
    ap.add_argument("--prefill-gen", type=int, default=8)
    ap.add_argument("--prefill-requests", type=int, default=6)
    ap.add_argument("--prefill-vocab", type=int, default=8192,
                    help="vocab size for the prefill ablation (0 keeps the "
                         "arch's own; smoke archs' 128 hides the per-step "
                         "LM-head cost chunking amortizes)")
    ap.add_argument("--layout", choices=["both", "contiguous", "paged"],
                    default="both",
                    help="scope the single-layout sections to one KV "
                         "layout (a CI matrix cell); 'both' also runs the "
                         "cross-layout ablation")
    ap.add_argument("--kv-dtype", choices=["f32", "bf16", "int8"],
                    default="f32",
                    help="KV-pool storage precision for the paged cells "
                         "(bf16: half-width storage through the same "
                         "kernels at 1/2 the resident bytes; int8: "
                         "per-(page, head)-scaled payload at 1/4, "
                         "dequantized inside the "
                         "attention kernels; contiguous cells stay f32)")
    ap.add_argument("--spec-k", default="2,4",
                    help="comma list of draft widths K for the speculative-"
                         "decoding ablation (0 skips it); each K runs "
                         "against a shared K=0 baseline per layout")
    ap.add_argument("--spec-drafter", default="prompt_lookup",
                    choices=["prompt_lookup", "hybrid_ssm"],
                    help="proposal source: n-gram prompt lookup (any "
                         "family) or the hybrid family's own Mamba layers")
    ap.add_argument("--spec-ngram", type=int, default=2,
                    help="prompt-lookup n-gram match length")
    ap.add_argument("--share-requests", type=int, default=8,
                    help="rows in the prefix-sharing ablation")
    ap.add_argument("--share-prefix-len", type=int, default=256,
                    help="shared system-prompt length for the "
                         "prefix-sharing ablation")
    ap.add_argument("--faults", action="store_true",
                    help="run the pressure cell: preemption + host spill "
                         "under a scripted FaultPlan (pool exhaustion, "
                         "cancel, deadline storm) with survivor "
                         "token-identity and conservation asserts")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="minimal sizes: CI driver-rot check, not a benchmark")
    ap.add_argument("--audit", action="store_true",
                    help="wrap every engine run in jit_cache_audit so an "
                         "accidental retrace fails loudly instead of "
                         "reporting bogus tok/s")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the result dict as JSON (token lists "
                         "dropped, tuple keys flattened) — the machine-"
                         "readable feed for benchmarks/perf_snapshot.py")
    args = ap.parse_args(argv)
    if args.family:
        args.kv_arch = {
            "dense": "qwen2.5-3b", "moe": "qwen3-moe-235b-a22b",
            "ssm": "mamba2-2.7b", "hybrid": "zamba2-2.7b",
        }[args.family] + "-smoke"
    if args.quick:
        args.requests, args.gen = 8, 16
        args.prompt_len, args.prefill_chunk = 64, 16
        args.prefill_requests = 4
        args.share_requests, args.share_prefix_len = 4, 64
    if args.smoke:
        args.requests, args.gen, args.batch = 3, 6, 2
        args.prompt_len = 20
        # keep the chunked path live (>1) at a smoke-sized width
        args.prefill_chunk = max(2, min(args.prefill_chunk, 8))
        args.prefill_requests, args.prefill_gen = 3, 4
        args.prefill_vocab = min(args.prefill_vocab, 512)
        # prefix sharing stays live too: 3 full pages shared across 4 rows
        # (page-aligned so the fully-shared request exercises CoW)
        args.share_requests = 4
        args.share_prefix_len = 3 * args.page_size

    cfg = get_arch(args.arch)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    reqs = make_requests(0, args.requests, cfg.vocab_size, args.gen)
    max_len = 12 + args.gen + 1

    if args.kv_dtype != "f32" and args.layout != "paged":
        print(f"note: --kv-dtype {args.kv_dtype} only applies to paged "
              f"pools; layout={args.layout} keeps its non-paged cells f32")
    main_cache = None
    host_cache = None
    if args.layout == "paged":
        main_cache = CacheConfig(layout="paged", page_size=args.page_size,
                                 kv_dtype=args.kv_dtype)
        if args.kv_dtype != "f32":
            # the main cell feeds prompts token-by-token on both sides
            # (EngineConfig default prefill_chunk=1), so giving the host
            # loop the same sub-f32 pool keeps the storage precision and
            # write grain — and therefore the token streams — identical
            host_cache = main_cache
    host = run_host_loop(model, params, reqs, args.batch, max_len,
                         cache=host_cache)
    eng = run_engine(model, params, reqs, args.batch, max_len,
                     args.steps_per_sync, audit=args.audit, cache=main_cache)

    # both schedulers must produce identical tokens before we compare speed
    for i in range(len(reqs)):
        a = [int(t) for t in host["outputs"][i]]
        b = [int(t) for t in eng["outputs"][i]]
        assert a == b, f"request {i}: host {a} != engine {b}"

    print(f"arch={args.arch} requests={args.requests} batch={args.batch} "
          f"gen={args.gen} steps_per_sync={args.steps_per_sync}"
          + (f" kv_dtype={args.kv_dtype}" if args.layout == "paged" else ""))
    print(f"  {'loop':<10} {'gen tok/s':>10} {'steps':>7} {'seconds':>8}")
    for name, r in (("host-loop", host), ("engine", eng)):
        print(f"  {name:<10} {r['tok_s']:>10.1f} {r['steps']:>7d} "
              f"{r['seconds']:>8.2f}")
    print(f"  speedup: {eng['tok_s'] / host['tok_s']:.2f}x "
          f"(outputs token-identical)")
    out = {"host": host, "engine": eng}
    if args.layout == "both":
        print()
        print("-- KV layout: paged vs contiguous (mixed prompt lengths) --")
        out["layouts"] = compare_layouts(args)
    print()
    print(f"-- Chunked prefill: prompt ingestion + TTFT "
          f"(layout={args.layout}) --")
    out["prefill"] = compare_prefill(args)
    print()
    print(f"-- Speculative decoding: draft + verify "
          f"(layout={args.layout}) --")
    out["spec"] = run_spec(args)
    if args.layout in ("both", "paged"):
        print()
        print("-- Prefix sharing: shared system prompt, CoW (paged) --")
        out["sharing"] = compare_prefix_sharing(args)
    if args.faults:
        print()
        print(f"-- Pressure: preemption/spill + FaultPlan "
              f"(layout={args.layout}) --")
        out["pressure"] = run_pressure(args)
    if args.json:
        import json

        with open(args.json, "w", encoding="utf-8") as f:
            json.dump(_jsonable(out), f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"\nwrote {args.json}")
    return out


def _jsonable(obj):
    """Result dict -> JSON-safe: tuple keys flattened ("layout:chunk"),
    per-request token lists dropped (the parity asserts already ran)."""
    if isinstance(obj, dict):
        return {
            (":".join(map(str, k)) if isinstance(k, tuple) else str(k)):
                _jsonable(v)
            for k, v in obj.items() if k != "outputs"
        }
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, (np.integer,)):
        return int(obj)
    if isinstance(obj, (np.floating,)):
        return float(obj)
    return obj


if __name__ == "__main__":
    main()
