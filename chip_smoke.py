#!/usr/bin/env python3
"""Chip smoke: serve qwen2.5-3b at its published widths on one TPU.

    python chip_smoke.py

Builds ``qwen2.5-3b`` uncut (36 layers, d_model 2048, 16/2 heads of 128,
d_ff 11008, vocab 151936, tied embeddings, bf16) with random weights from
a fixed seed, and serves 8 seeded requests (prompts of 128-512 tokens, 32
new tokens each) through ``ServingEngine`` with the paged KV cache and
chunked prefill, every op on its Pallas kernel as Mosaic compiles it.  It
then checks that

* every request returned exactly the number of tokens it asked for;
* no jitted engine entry point compiled twice (``jit_cache_audit``);
* one request's logits through the serving cache path (chunked prefill,
  then decode steps, on the Pallas kernels) agree with a plain forward of
  the same tokens on the reference backend, within the tolerance that
  ``logit_tolerance`` states;
* every token the engine emitted, for every request, is greedy under the
  reference forward of that request's prompt and emitted tokens, up to
  twice that tolerance.

Earlier lines report the device, byte counts, compile seconds per jitted
function (and whether JAX's persistent cache served it) and the serve
phase's wall time.  The last line is one JSON object naming the device.
Where JAX finds no TPU, or Pallas would run in interpret mode, the script
exits non-zero before building anything and prints no result.  Everything
runs in this one process.
"""
from __future__ import annotations

import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import monitoring  # noqa: E402

from repro.analysis.audit import jit_cache_audit  # noqa: E402
from repro.configs.registry import get_arch  # noqa: E402
from repro.core.compile_cache import enable_compile_cache  # noqa: E402
from repro.core.policy import interpret_default, use_backend  # noqa: E402
from repro.models import lm as LM  # noqa: E402
from repro.models.model import build_model  # noqa: E402
from repro.serving import CacheConfig, EngineConfig, ServingEngine  # noqa: E402

ARCH = "qwen2.5-3b"

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    """Backend compile seconds per jitted function, from JAX's monitoring
    events, and how many of those compiles the persistent cache served.

    JAX records a cache hit inside the compile it belongs to, just before
    that compile's duration event, so a hit is charged to the next
    function whose duration arrives."""

    def __init__(self) -> None:
        self.seconds: dict[str, float] = {}
        self.count: dict[str, int] = {}
        self.hits: dict[str, int] = {}
        self._pending_hit = False

    def _on_event(self, event: str, **_) -> None:
        if event == _CACHE_HIT_EVENT:
            self._pending_hit = True

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event != _COMPILE_EVENT:
            return
        name = str(kw.get("fun_name", "?"))
        self.seconds[name] = self.seconds.get(name, 0.0) + duration
        self.count[name] = self.count.get(name, 0) + 1
        self.hits[name] = self.hits.get(name, 0) + int(self._pending_hit)
        self._pending_hit = False

    def __enter__(self) -> "CompileLog":
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def __exit__(self, *exc) -> None:
        monitoring.unregister_event_listener(self._on_event)
        monitoring.unregister_event_duration_listener(self._on_duration)


def logit_tolerance(cfg, ref_rms: float, n_compared: int) -> float:
    """Largest |engine - reference| logit difference the check admits.

    Both sides hold the same bf16 weights and accumulate every matmul in
    f32; they differ in where activations are rounded to bf16 (a fused
    Pallas kernel rounds once per output, the reference once per jnp op)
    and in the order of f32 reductions.  A rounding of relative size
    u = 2**-8 (bf16's unit roundoff) can differ at each of the 12 bf16
    outputs of a dense layer (two norms, q, k, v, attention, output
    projection, gate, up, their product, down, two residual adds), and
    the differences add up like a random walk: the hidden state before
    the head, and through the head each logit, differs by a near-Gaussian
    error of at most about sigma = u * sqrt(12 * n_layers) times the
    logits' RMS.  The largest of ``n_compared`` such errors stays below
    sigma * sqrt(2 ln n_compared).  That product is the bound.  (Served
    on CPU, bf16 models at d_model 256 came to 0.36 of it at 36 layers
    and to 0.48 of it at 9; qwen2.5-3b uncut on a TPU v5e came to 0.28
    of it.)"""
    u = 2.0 ** -8
    sigma = u * float(np.sqrt(12 * cfg.n_layers)) * ref_rms
    return sigma * float(np.sqrt(2.0 * np.log(n_compared)))


def _peak_bytes(dev) -> int | None:
    stats = dev.memory_stats()
    return None if stats is None else int(stats.get("peak_bytes_in_use", 0))


def _nbytes(tree) -> int:
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


@functools.partial(jax.jit, static_argnums=(0, 4))
def _forward_logits(cfg, params, tokens, starts, width: int):
    """(N, width, V) logits of the plain forward of each (L,) row of
    ``tokens`` at positions ``starts[n] .. starts[n] + width - 1``; the
    row at position t predicts ``tokens[n, t + 1]``.  Rows may be padded
    past their end: the forward is causal."""
    h = LM.forward(cfg, params, tokens, remat=False)
    idx = starts[:, None] + jnp.arange(width)[None, :]
    h = jnp.take_along_axis(h, idx[:, :, None], axis=1)
    return LM.lm_logits(cfg, params, h)


def _cache_path_logits(model, params, tokens, n_prompt: int, *, cache,
                       chunk: int):
    """(S - 1, V) logits of one (S,) sequence through the serving cache:
    ``tokens[:n_prompt]`` in ``chunk``-wide ``prefill_chunk`` steps (the
    engine's prefill partition), then one ``decode_step`` per remaining
    token, over a batch-1 decode state built from the same ``cache``
    config.  Row t predicts ``tokens[t + 1]``."""
    state = model.init_decode_state(1, len(tokens), per_row_pos=True,
                                    cache=cache)
    prefill = jax.jit(
        lambda p, s, t, w: model.prefill_chunk(p, s, t, w, logits_all=True),
        donate_argnums=(1,),
    )
    decode = jax.jit(model.decode_step, donate_argnums=(1,))
    out = []
    for start in range(0, n_prompt, chunk):
        width = min(chunk, n_prompt - start)
        feed = np.zeros((1, chunk), np.int32)
        feed[0, :width] = tokens[start:start + width]
        logits, state = prefill(params, state, jnp.asarray(feed),
                                jnp.full((1,), width, jnp.int32))
        out.append(logits[0, :width])
    for t in range(n_prompt, len(tokens) - 1):
        logits, state = decode(params, state, jnp.asarray(tokens[t:t + 1]))
        out.append(logits)
    return jnp.concatenate(out, axis=0)


def _check_logits(cfg, got, want) -> dict:
    """Raise unless the cache path's (S, V) logits ``got`` are within
    ``logit_tolerance`` of the reference's ``want`` at every element."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    err, rms, rms_err, agree = jax.device_get((
        jnp.max(jnp.abs(got - want)),
        jnp.sqrt(jnp.mean(jnp.square(want))),
        jnp.sqrt(jnp.mean(jnp.square(got - want))),
        jnp.sum(jnp.argmax(got, -1) == jnp.argmax(want, -1)),
    ))
    tol = logit_tolerance(cfg, float(rms), int(want.size))
    print(f"cache-path logits vs reference over {want.shape[0]} positions x "
          f"{want.shape[1]} vocab: max |diff| {float(err):.5f}, tolerance "
          f"{tol:.5f}; RMS diff {float(rms_err):.5f}, reference RMS "
          f"{float(rms):.5f}; argmax agreement {int(agree)}/{want.shape[0]}")
    if not err <= tol:
        raise AssertionError(f"cache-path logits differ from the reference "
                             f"by {float(err)} > {tol}")
    return {"max_logit_err": float(err), "logit_rms": float(rms),
            "logit_tol": tol}


def _check_emitted(cfg, want, emitted) -> dict:
    """Raise unless every emitted token is greedy under the reference.

    ``want`` (N, gen, V) holds the reference logits that chose each of
    the (N, gen) ``emitted`` tokens.  The engine takes the argmax of its
    own logits; if those are within ``tol`` of the reference's at every
    element, its token's reference logit is within ``2 * tol`` of the
    reference maximum.  A token from the wrong row, position or context
    passes one position only by landing that close to the maximum: the
    printed chance is the share of the vocabulary that does, averaged
    over positions."""
    want = want.astype(jnp.float32)
    rms = float(jnp.sqrt(jnp.mean(jnp.square(want))))
    bound = 2.0 * logit_tolerance(cfg, rms, int(want.size))
    top = jnp.max(want, axis=-1)
    at = jnp.take_along_axis(want, emitted[..., None], axis=-1)[..., 0]
    slack, greedy, near = jax.device_get((
        top - at,
        jnp.sum(at == top),
        jnp.mean(jnp.sum(want >= (top - bound)[..., None], axis=-1)
                 / want.shape[-1]),
    ))
    print(f"emitted tokens vs reference over {slack.size} served positions "
          f"({slack.shape[0]} requests): max shortfall from the reference "
          f"max {float(slack.max()):.5f}, bound {bound:.5f}; "
          f"{int(greedy)}/{slack.size} are the reference argmax; a token at "
          f"random would pass one position with chance {float(near):.3g}")
    bad = np.argwhere(slack > bound)
    if bad.size:
        raise AssertionError(
            f"engine emitted tokens the reference ranks more than {bound} "
            f"below its maximum at (request, token) {bad.tolist()[:8]}")
    return {"max_emitted_shortfall": float(slack.max()),
            "emitted_bound": bound, "chance_per_position": float(near)}


def serve_and_check(
    arch: str = ARCH,
    *,
    n_requests: int = 8,
    prompt_lens: tuple = (128, 512),
    gen: int = 32,
    page_size: int = 16,
    prefill_chunk: int = 128,
    steps_per_sync: int = 8,
) -> dict:
    """Serve ``n_requests`` seeded requests through the engine under the
    Pallas backend and check the outputs against the reference backend.

    Weights and requests come from seed 0.  Raises on any failed check;
    returns the figures it printed."""
    dev = jax.devices()[0]
    cfg = get_arch(arch)
    model = build_model(cfg)
    with CompileLog() as compiles:
        t0 = time.perf_counter()
        params = jax.block_until_ready(
            model.init_params(jax.random.PRNGKey(0)))
        report = {
            "param_bytes": _nbytes(params),
            "init_s": time.perf_counter() - t0,
            "peak_bytes_after_init": _peak_bytes(dev),
        }
        print(f"params: {report['param_bytes']} bytes, init "
              f"{report['init_s']:.3f} s, peak after init "
              f"{report['peak_bytes_after_init']} bytes")

        rng = np.random.default_rng(0)
        lens = rng.integers(prompt_lens[0], prompt_lens[1] + 1, n_requests)
        prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
                   for n in lens]
        cache = CacheConfig(layout="paged", page_size=page_size)
        config = EngineConfig(prefill_chunk=prefill_chunk,
                              steps_per_sync=steps_per_sync)
        with use_backend("pallas"):
            max_len = int(lens.max()) + gen
            eng = ServingEngine(model, params, batch=n_requests,
                                max_len=max_len, cache=cache, config=config)
            rids = [eng.submit(p.tolist(), gen) for p in prompts]
            t0 = time.perf_counter()
            with jit_cache_audit(eng) as audit:
                outs = eng.run()
            report["serve_s"] = time.perf_counter() - t0
            bad = {int(r): len(outs.get(r, ())) for r in rids
                   if len(outs.get(r, ())) != gen}
            if bad:
                raise AssertionError(
                    f"requests returned the wrong token counts (want {gen}): "
                    f"{bad}")
            pools = {k: v for k, v in eng._mstate.items()
                     if k in ("kp", "vp", "ksc", "vsc")}
            host = {k: v for k, v in eng._mstate.items()
                    if k in ("hkp", "hvp", "hksc", "hvsc")}
            report["kv_pool_bytes"] = _nbytes(pools)
            report["host_tier_bytes"] = _nbytes(host)
            print(f"prompts: {sorted(int(n) for n in lens)} tokens, "
                  f"{gen} new tokens each; {eng.prefill_steps} prefill + "
                  f"{eng.steps} decode steps")
            print(f"KV pool on device: {report['kv_pool_bytes']} bytes; "
                  f"'host' tier (also device arrays): "
                  f"{report['host_tier_bytes']} bytes")
            print(f"serve phase wall time (smoke timing, not a metric): "
                  f"{report['serve_s']:.3f} s")
            print(f"jit_cache_audit: growth "
                  + ", ".join(f"{k}={audit.growth(k)}"
                              for k in sorted(audit.starts)))

            emitted = np.asarray([outs[r] for r in rids], np.int32)
            seqs = np.zeros((n_requests, max_len), np.int32)
            for i, p in enumerate(prompts):
                seqs[i, :len(p) + gen] = np.concatenate([p, emitted[i]])
            got = _cache_path_logits(model, params, seqs[0, :lens[0] + gen],
                                     int(lens[0]), cache=cache,
                                     chunk=prefill_chunk)
        with use_backend("reference"):
            want = _forward_logits(cfg, params, jnp.asarray(seqs[:1]),
                                   jnp.zeros((1,), jnp.int32),
                                   int(lens[0]) + gen - 1)[0]
            # the logits that chose each emitted token: position
            # n_prompt - 1 + j predicts the j-th generated token
            served = _forward_logits(cfg, params, jnp.asarray(seqs),
                                     jnp.asarray(lens - 1, jnp.int32), gen)
        report.update(_check_logits(cfg, got, want))
        report.update(_check_emitted(cfg, served, jnp.asarray(emitted)))

    report["peak_bytes"] = _peak_bytes(dev)
    report["cache_hits"] = sum(compiles.hits.values())
    report["compile_s"] = {}
    for attr in sorted(audit.starts):
        name = f"jit({getattr(eng, attr).__name__})"
        report["compile_s"][attr] = compiles.seconds.get(name, 0.0)
        print(f"compile {attr}: {compiles.seconds.get(name, 0.0):.3f} s, "
              f"{compiles.hits.get(name, 0)} of {compiles.count.get(name, 0)} "
              f"compile(s) from the persistent cache")
    print(f"all compiles: {sum(compiles.seconds.values()):.3f} s over "
          f"{sum(compiles.count.values())}, {report['cache_hits']} from the "
          f"persistent cache")
    print(f"peak_bytes_in_use: {report['peak_bytes']}")
    return report


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu" or interpret_default():
        print(f"chip_smoke: needs a TPU with Mosaic-compiled Pallas kernels; "
              f"JAX found platform {dev.platform!r}", file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}")
    print(f"device_kind: {dev.device_kind}")
    serve_and_check()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
