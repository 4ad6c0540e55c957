"""Plain reference forward passes, written from the layer equations.

``logits(arch, params, tokens, positions)`` runs one whole sequence in
float32 at ``highest`` matmul precision, with no kernel, cache, chunking
or batching, and returns the logits at ``positions`` (the position that
predicts token ``t + 1`` is ``t``).  It imports nothing of the program:
it reads the weights the benchmark made (``weights.py``), casting each
layer's leaves to float32 inside the scan over depth, so it fits beside
them on one chip.

Dense (Qwen2): RMSNorm, q/k/v projections with bias, rotary embedding
(halves rotated, base ``rope_theta``), grouped-query causal softmax
attention scaled by 1/sqrt(head_dim), output projection, residual;
RMSNorm, SwiGLU MLP, residual; final RMSNorm; logits against the tied
embedding.

``quantize`` is the control: every projection and the embedding rounded,
before use, to the precision below the one the configuration states:
``"fp8"`` (float8_e4m3fn, one scale per output channel) below bfloat16,
``"bf16"`` below float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from arch import Arch

F32 = jnp.float32


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _fp8(w, axis):
    """Round ``w`` to float8_e4m3fn, one scale per slice along ``axis``
    (the reduction axis of its matmul), and back to float32."""
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _prep(leaf, quantize, axis=0):
    w = leaf.astype(F32)
    if w.ndim != 2 or not quantize:
        return w
    if quantize == "fp8":
        return _fp8(w, axis)
    return w.astype(jnp.bfloat16).astype(F32)     # "bf16"


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = pos.astype(F32)[:, None] * inv            # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _dense_layer(arch: Arch, quantize, x, lp):
    s = x.shape[0]
    h, hkv, hd = arch.n_heads, arch.n_kv_heads, arch.head_dim
    a = {k: _prep(v, quantize) for k, v in lp["attn"].items()}
    m = {k: _prep(v, quantize) for k, v in lp["mlp"].items()}
    pos = jnp.arange(s)
    xn = _rms(x, a["ln"], arch.eps)
    q = (xn @ a["wq"] + a["bq"]).reshape(s, h, hd)
    k = (xn @ a["wk"] + a["bk"]).reshape(s, hkv, hd)
    v = (xn @ a["wv"] + a["bv"]).reshape(s, hkv, hd)
    q, k = _rope(q, pos, arch.rope_theta), _rope(k, pos, arch.rope_theta)
    qg = q.reshape(s, hkv, h // hkv, hd)
    sc = jnp.einsum("qhgd,khd->hgqk", qg, k) / jnp.sqrt(F32(hd))
    sc = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :],
                   sc, -jnp.inf)
    o = jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(sc, -1), v)
    x = x + o.reshape(s, h * hd) @ a["wo"]
    xn = _rms(x, m["ln"], arch.eps)
    return x + (jax.nn.silu(xn @ m["wg"]) * (xn @ m["wi"])) @ m["wo"]


@functools.partial(jax.jit, static_argnums=(0, 4))
def logits(arch: Arch, params, tokens, positions, quantize: str = ""):
    """(K, V) float32 logits of the plain forward of ``tokens`` (S,) at
    ``positions`` (K,).  Tokens past the sequence's end may be anything:
    the forward is causal."""
    with jax.default_matmul_precision("highest"):
        emb = _prep(params["embed"], quantize, axis=1)    # (V, d)
        x = emb[tokens]
        x, _ = jax.lax.scan(
            lambda x, lp: (_dense_layer(arch, quantize, x, lp), None),
            x, params["layers"])
        hid = _rms(x[positions], params["ln_f"].astype(F32), arch.eps)
        return hid @ emb.T


def gaps(ref_logits, tokens):
    """How far below the reference's best logit each chosen token lies."""
    picked = jnp.take_along_axis(ref_logits, tokens[:, None], axis=1)[:, 0]
    return jnp.max(ref_logits, axis=1) - picked
