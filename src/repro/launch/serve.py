"""Serving driver — a thin CLI over the continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-2.7b-smoke \
        --batch 4 --prompt-len 16 --gen 32

Greedy decode over the synthetic token distribution; reports tokens/s and
verifies the cache path incrementally matches teacher-forced prefill
(--check) — the serving analogue of the paper's layer-by-layer regression
testing.  LM families run through ``repro.serving.ServingEngine`` (device-
side control state, one host sync per batch of steps); families without
per-row decode state (vlm, encdec) fall back to the lockstep loop.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.registry import get_arch
from repro.core.compile_cache import enable_compile_cache
from repro.models import lm as LM
from repro.models.model import build_model
from repro.serving import ServingEngine, configs_from_flags
from repro.serving.checks import assert_decode_matches_teacher_forced


def _serve_engine(model, params, prompt, args) -> int:
    """Continuous-batching path: every request enters through the queue."""
    max_len = args.prompt_len + args.gen + 1
    cache, config = configs_from_flags(args)
    eng = ServingEngine(
        model, params, batch=args.batch, max_len=max_len,
        cache=cache, config=config,
    )
    rids = [
        eng.submit(prompt[b].tolist(), args.gen) for b in range(args.batch)
    ]
    t0 = time.time()
    outs = eng.run()
    dt = time.time() - t0
    total_tokens = args.batch * (args.prompt_len + args.gen)
    print(f"decoded {args.gen} tokens x batch {args.batch} "
          f"in {dt:.2f}s ({total_tokens/dt:.1f} tok/s incl. prefill, "
          f"{eng.steps} decode + {eng.prefill_steps} prefill steps)")
    if eng.ttft:
        ttft = sum(eng.ttft.values()) / len(eng.ttft)
        print(f"mean TTFT {1e3 * ttft:.1f} ms "
              f"(prefill chunk {args.prefill_chunk})")
    s = eng.stats()
    if "kv_pages" in s:   # attention-free archs have no pages to report
        print(f"paged KV: peak {int(s['kv_pages_peak'])}/{int(s['kv_pages'])} "
              f"pages ({int(s['kv_resident_bytes_peak'])} resident bytes)")
    if "snap_slots" in s:   # recurrent families under prefix sharing
        print(f"state snapshots: peak {int(s['snap_slots_peak'])}/"
              f"{int(s['snap_slots'])} page-boundary slots resident")
    if "shared_prompt_tokens" in s:
        print(f"prefix sharing: {int(s['shared_prompt_tokens'])} prompt "
              f"tokens served from shared pages/snapshots "
              f"({int(s['cow_pages'])} CoW copies)")
    if "spec_accept_rate" in s:
        print(f"speculation: {int(s['spec_accepted'])}/"
              f"{int(s['spec_proposed'])} drafts accepted "
              f"({s['spec_accept_rate']:.0%}), "
              f"{int(s['spec_emitted'])} tokens via verify steps")
    print("sample:", outs[rids[0]][:16].tolist())
    return 0


def _serve_lockstep(model, params, prompt, args, cfg) -> int:
    """Legacy lockstep loop for families without per-row decode state."""
    max_len = args.prompt_len + args.gen + 1
    decode = jax.jit(model.decode_step, donate_argnums=(1,))
    state = model.init_decode_state(args.batch, max_len)
    if cfg.family == "vlm":
        vision = jnp.zeros((args.batch, cfg.n_vision_tokens, cfg.d_model),
                           cfg.dtype_())
        state = LM.prefill_vlm_cross_cache(cfg, params, vision, state)

    t0 = time.time()
    logits = None
    for i in range(args.prompt_len):
        logits, state = decode(params, state, prompt[:, i])
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    generated = [tok]
    for _ in range(args.gen - 1):
        logits, state = decode(params, state, tok)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        generated.append(tok)
    jax.block_until_ready(tok)
    dt = time.time() - t0
    total_tokens = args.batch * (args.prompt_len + args.gen)
    print(f"decoded {args.gen} tokens x batch {args.batch} "
          f"in {dt:.2f}s ({total_tokens/dt:.1f} tok/s incl. prefill)")
    gen = jnp.stack(generated, axis=1)
    print("sample:", gen[0, :16].tolist())
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--steps-per-sync", type=int, default=8)
    ap.add_argument("--layout", choices=["contiguous", "paged"],
                    default="contiguous",
                    help="KV-cache layout (paged: pool+block-table)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--n-pages", type=int, default=None,
                    help="page-pool size (default: batch*max_len/page_size)")
    ap.add_argument("--kv-dtype", choices=["f32", "bf16", "int8"],
                    default="f32",
                    help="KV-pool storage precision (needs --layout paged "
                         "below f32; bf16 = 1/2 the f32 resident bytes, "
                         "int8 = 1/4 via per-(page, head)-scaled payload)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; >0 samples with per-request keys")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--prefill-chunk", type=int, default=1,
                    help="prompt tokens ingested per engine step (chunked "
                         "prefill; 1 = token-by-token)")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="page-level prompt prefix sharing (needs --layout "
                         "paged): attention families alias pages with "
                         "copy-on-write; recurrent families (ssm/hybrid) "
                         "restore page-boundary state snapshots")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: draft K tokens per row per "
                         "step and verify them through the chunked prefill "
                         "path (0 = off; needs --prefill-chunk >= 2, "
                         "greedy only)")
    ap.add_argument("--spec-drafter", default="prompt_lookup",
                    choices=["prompt_lookup", "hybrid_ssm"],
                    help="draft source: n-gram prompt lookup (any family) "
                         "or the hybrid family's own Mamba layers")
    ap.add_argument("--spec-ngram", type=int, default=2,
                    help="prompt-lookup n-gram match length")
    ap.add_argument("--check", action="store_true",
                    help="verify decode path against teacher-forced forward")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_arch(args.arch)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    prompt = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0, cfg.vocab_size
    )

    if cfg.family in ("dense", "moe", "ssm", "hybrid"):
        rc = _serve_engine(model, params, prompt, args)
    else:
        if (args.layout != "contiguous" or args.temperature > 0 or args.top_k
                or args.prefix_sharing or args.spec_k):
            print(f"warning: --layout/--temperature/--top-k/--prefix-sharing/"
                  f"--spec-k are engine features; the {cfg.family} fallback "
                  f"loop is lockstep greedy over the contiguous cache and "
                  f"ignores them")
        rc = _serve_lockstep(model, params, prompt, args, cfg)

    if args.check and cfg.family in ("dense", "moe", "ssm", "hybrid"):
        assert_decode_matches_teacher_forced(
            model, params, prompt, args.prompt_len + args.gen + 1
        )
        print("decode path matches teacher-forced forward ✓")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
