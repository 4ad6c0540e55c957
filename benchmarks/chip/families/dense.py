"""The dense family: a decoder of grouped-query attention and a SwiGLU MLP
(Qwen2 and its like), as the benchmark sees it.

Sizes (:class:`Arch`, read from the published ``config.json`` keys), the
program attributes they must match, the weight table, the plain reference
forward and the work the traffic asks of each layer.  It imports nothing
of the program under test.

Weights: projections draw N(0, 1/fan_in); the embedding N(0, 0.02), as
Qwen2's initializer range; an untied head N(0, 1/d_model); norm weights
1 + N(0, 0.1) and q/k/v biases N(0, 0.1), so that neither is a no-op the
check could not see.

Reference: RMSNorm, q/k/v projections (with bias where the model has
it), rotary embedding (halves rotated, base ``rope_theta``), grouped-query
causal softmax attention scaled by 1/sqrt(head_dim), output projection,
residual; RMSNorm, SwiGLU MLP, residual; final RMSNorm; logits against
the tied embedding or the head.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

import work as W
from reference import F32, _prep, _rms
from weights import dtype_of


@dataclass(frozen=True)
class Arch:
    n_layers: int
    d_model: int
    vocab: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    rope_theta: float
    qkv_bias: bool
    tied: bool
    eps: float
    dtype: str             # weights and activations as served


def arch_of(cfg: dict) -> Arch:
    """The :class:`Arch` of a configuration file.  Qwen2's ``config.json``
    has no key for the q/k/v biases its attention always carries, so
    ``attention_bias``, where a file gives it, defaults to that."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qwen2 = "Qwen2ForCausalLM" in cfg.get("architectures", ())
    return Arch(
        n_layers=cfg["num_hidden_layers"], d_model=d, vocab=cfg["vocab_size"],
        n_heads=h, n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim", d // h), d_ff=cfg["intermediate_size"],
        rope_theta=cfg["rope_theta"],
        qkv_bias=bool(cfg.get("attention_bias", qwen2)),
        tied=bool(cfg["tie_word_embeddings"]), eps=cfg["rms_norm_eps"],
        dtype=cfg["torch_dtype"])


def program_sizes(a: Arch) -> dict:
    """``{ArchConfig attribute: value}`` the program's model must have."""
    return {"family": "dense", "n_layers": a.n_layers, "d_model": a.d_model,
            "vocab_size": a.vocab, "dtype": a.dtype, "n_heads": a.n_heads,
            "n_kv_heads": a.n_kv_heads, "head_dim_": a.head_dim,
            "d_ff": a.d_ff, "rope_theta": a.rope_theta,
            "qkv_bias": a.qkv_bias, "tie_embeddings": a.tied}


# --------------------------------------------------------------------------
# weights
# --------------------------------------------------------------------------

def shapes(arch: Arch) -> dict:
    """``{path: (shape, dtype, kind, scale)}`` of every leaf."""
    d, L, dt = arch.d_model, arch.n_layers, dtype_of(arch)
    out = {
        "embed": ((arch.vocab, d), dt, "normal", 0.02),
        "ln_f": ((d,), dt, "norm", 0.1),
    }
    if not arch.tied:
        out["lm_head"] = ((d, arch.vocab), dt, "normal", d ** -0.5)
    h, hkv, hd, ff = arch.n_heads, arch.n_kv_heads, arch.head_dim, arch.d_ff
    a, m = "layers/attn/", "layers/mlp/"
    out.update({
        a + "wq": ((L, d, h * hd), dt, "normal", d ** -0.5),
        a + "wk": ((L, d, hkv * hd), dt, "normal", d ** -0.5),
        a + "wv": ((L, d, hkv * hd), dt, "normal", d ** -0.5),
        a + "wo": ((L, h * hd, d), dt, "normal", (h * hd) ** -0.5),
        a + "ln": ((L, d), dt, "norm", 0.1),
        m + "wg": ((L, d, ff), dt, "normal", d ** -0.5),
        m + "wi": ((L, d, ff), dt, "normal", d ** -0.5),
        m + "wo": ((L, ff, d), dt, "normal", ff ** -0.5),
        m + "ln": ((L, d), dt, "norm", 0.1),
    })
    if arch.qkv_bias:
        out.update({
            a + "bq": ((L, h * hd), dt, "normal", 0.1),
            a + "bk": ((L, hkv * hd), dt, "normal", 0.1),
            a + "bv": ((L, hkv * hd), dt, "normal", 0.1),
        })
    return out


# --------------------------------------------------------------------------
# the plain reference
# --------------------------------------------------------------------------

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

    def proj(w, b):
        y = xn @ a[w]
        return y + a[b] if arch.qkv_bias else y

    q = proj("wq", "bq").reshape(s, h, hd)
    k = proj("wk", "bk").reshape(s, hkv, hd)
    v = proj("wv", "bv").reshape(s, hkv, hd)
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
        head = emb.T if arch.tied else _prep(params["lm_head"], quantize)
        return hid @ head


# --------------------------------------------------------------------------
# work
# --------------------------------------------------------------------------

def matmul_params(arch: Arch) -> int:
    """Weights that every token multiplies, the logits' head excluded."""
    d, h, hkv, hd = arch.d_model, arch.n_heads, arch.n_kv_heads, arch.head_dim
    attn = d * h * hd + 2 * d * hkv * hd + h * hd * d
    return arch.n_layers * (attn + 3 * d * arch.d_ff)


def head_params(arch: Arch) -> int:
    return arch.vocab * arch.d_model


def kv_bytes_per_token(arch: Arch) -> int:
    """K and V of one position, all layers."""
    return (2 * arch.n_layers * arch.n_kv_heads * arch.head_dim
            * W.ITEM[arch.dtype])


def attention(arch: Arch, first: int, last: int, *, chunk: int) -> W.Work:
    """Attention over positions [first, last) of one request, fed in
    pieces of ``chunk`` positions (1 for decode): 4 * ctx * Hq * hd
    operations per token and layer (q.k and p.v), and the live K and V
    of the context read once per piece."""
    if last <= first:
        return W.Work()
    flops = 4.0 * W.ctx_sum(first, last) * arch.n_heads * arch.head_dim \
        * arch.n_layers
    nbytes = 0.0
    for start in range(first, last, chunk):
        end = min(start + chunk, last)
        nbytes += end * kv_bytes_per_token(arch)
    return W.Work(flops, nbytes)


def gemm(arch: Arch, tokens: int, head_tokens: int, dispatches: int) -> W.Work:
    """Projections: 2 * tokens * weights operations.  Bytes: the weights
    read once per dispatch, and every token's input and output rows."""
    item = W.ITEM[arch.dtype]
    flops = 2.0 * (tokens * matmul_params(arch) + head_tokens * head_params(arch))
    w_bytes = (matmul_params(arch) + head_params(arch)) * item * dispatches
    d, h, hkv, hd, ff = (arch.d_model, arch.n_heads, arch.n_kv_heads,
                         arch.head_dim, arch.d_ff)
    rows = (d + h * hd) + (d + 2 * hkv * hd) + (h * hd + d) \
        + 2 * (d + ff) + (ff + d)
    act = (tokens * rows * arch.n_layers + head_tokens * (d + arch.vocab)) \
        * item
    return W.Work(flops, w_bytes + act)


def model_flops(arch: Arch, tokens: int, head_tokens: int,
                attn_ctx: int) -> float:
    """Operations the model needs: projections, the head on the positions
    whose logits are used, and attention over ``attn_ctx`` summed
    contexts."""
    f = 2.0 * (tokens * matmul_params(arch) + head_tokens * head_params(arch))
    return f + 4.0 * attn_ctx * arch.n_heads * arch.head_dim * arch.n_layers


def window_work(arch: Arch, recs, chunk: int, dispatches: int) -> dict:
    """Work of the requests ``recs`` (each with ``plen`` and ``max_new``)
    by layer: attention per phase, the projections, and the model's
    operations.  Each prompt is fed in pieces of ``chunk`` positions, then
    each generated token but the last one at a time; the weights are read
    once per dispatch of a step program."""
    att_pre, att_dec = W.Work(), W.Work()
    tokens = head = ctx = 0
    for r in recs:
        fed = r.plen + r.max_new - 1          # positions fed to the model
        att_pre += attention(arch, 0, r.plen, chunk=chunk)
        att_dec += attention(arch, r.plen, fed, chunk=1)
        tokens += fed
        head += r.max_new                     # positions whose logits are used
        ctx += W.ctx_sum(0, fed)
    return {
        "attention": (att_pre, att_dec),
        "gemm": gemm(arch, tokens, head, dispatches),
        "model_flops": model_flops(arch, tokens, head, ctx),
    }
