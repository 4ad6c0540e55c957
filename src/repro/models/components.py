"""Reusable model components for the architecture zoo.

Everything is built on the portable ops (``repro.kernels.ops``) so each
architecture is single-source across backends — the paper's property,
generalized from Caffe blocks to transformer blocks.

Sharding: activations/params pass through ``shard`` hints (no-ops without a
mesh) so the same code lowers on 1 CPU device and on the 512-chip mesh.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.distributed.sharding import shard
from repro.kernels import ops


Params = Dict[str, jax.Array]


def dense(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None):
    """Projection over the last axis via the portable matmul."""
    lead = x.shape[:-1]
    y = ops.matmul(x.reshape(-1, x.shape[-1]), w)
    if b is not None:
        y = ops.bias_add_rows(y, b)
    return y.reshape(*lead, w.shape[-1])


def norm(cfg: ArchConfig, w: jax.Array, x: jax.Array) -> jax.Array:
    return ops.rmsnorm(x, w, cfg.norm_eps)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(cfg: ArchConfig, positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """positions (...,) -> cos/sin (..., head_dim//2), f32."""
    hd = cfg.head_dim_
    inv = 1.0 / (
        cfg.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    )
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, D); cos/sin: (S, D/2) or (B, D/2) for decode."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    while cos.ndim < x.ndim:
        cos, sin = cos[..., None, :], sin[..., None, :]  # broadcast over H
        if cos.ndim < x.ndim:
            cos, sin = jnp.expand_dims(cos, 0), jnp.expand_dims(sin, 0)
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention block (self / cross, train / decode)
# ---------------------------------------------------------------------------

def init_attention(cfg: ArchConfig, rng, *, cross: bool = False) -> Params:
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    ks = jax.random.split(rng, 4)
    s = 1.0 / np.sqrt(d)
    dt = cfg.dtype_()
    p = {
        "wq": (jax.random.normal(ks[0], (d, h * hd)) * s).astype(dt),
        "wk": (jax.random.normal(ks[1], (d, hkv * hd)) * s).astype(dt),
        "wv": (jax.random.normal(ks[2], (d, hkv * hd)) * s).astype(dt),
        "wo": (jax.random.normal(ks[3], (h * hd, d)) * s).astype(dt),
        "ln": jnp.ones((d,), dt),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((h * hd,), dt)
        p["bk"] = jnp.zeros((hkv * hd,), dt)
        p["bv"] = jnp.zeros((hkv * hd,), dt)
    if cross:
        p["gate"] = jnp.zeros((), dt)  # llama-vision tanh gate
    return p


def attention_block(
    cfg: ArchConfig,
    p: Params,
    x: jax.Array,                       # (B, S, d)
    *,
    positions: Optional[jax.Array] = None,
    causal: bool = True,
    kv_src: Optional[jax.Array] = None,  # cross-attention memory (B, Sk, d)
    window: Optional[int] = None,
) -> jax.Array:
    b, s, d = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    xn = norm(cfg, p["ln"], x)
    # SP gather-once: norm runs on the seq-sharded residual; the normed
    # activation is gathered ONCE here and reused by all three projections
    # (instead of GSPMD re-gathering per dot — perf iteration L1, §Perf).
    xn = shard(xn, ("data", None, None))
    src = norm(cfg, p["ln"], kv_src) if kv_src is not None else xn
    q = dense(xn, p["wq"], p.get("bq")).reshape(b, s, h, hd)
    k = dense(src, p["wk"], p.get("bk")).reshape(b, src.shape[1], hkv, hd)
    v = dense(src, p["wv"], p.get("bv")).reshape(b, src.shape[1], hkv, hd)
    if kv_src is None:  # self-attention: rotary positions
        if positions is None:
            positions = jnp.arange(s)
        cos, sin = rope_freqs(cfg, positions)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    # TP over q heads when divisible; KV heads replicate across TP when the
    # GQA group count is below the TP degree (Megatron GQA convention).
    from repro.distributed.sharding import axis_size
    tp = axis_size("model")
    q = shard(q, ("data", None, "model" if h % max(tp, 1) == 0 else "auto", None))
    kv_axis = "model" if hkv % max(tp, 1) == 0 else None
    k = shard(k, ("data", None, kv_axis, None))
    v = shard(v, ("data", None, kv_axis, None))
    o = ops.attention(
        q, k, v, causal=causal and kv_src is None, window=window
    )
    o = dense(o.reshape(b, s, h * hd), p["wo"])
    if "gate" in p:
        o = jnp.tanh(p["gate"]) * o
    return x + o


def attention_decode_block(
    cfg: ArchConfig,
    p: Params,
    x: jax.Array,                       # (B, d) one token
    cache_k: jax.Array,                 # (B, Smax, Hkv, hd)
    cache_v: jax.Array,
    pos: jax.Array,                     # scalar int32
    *,
    window: Optional[int] = None,
    cross: bool = False,
    cross_len: Optional[jax.Array] = None,
):
    b, d = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    xn = norm(cfg, p["ln"], x)
    q = dense(xn, p["wq"], p.get("bq")).reshape(b, h, hd)
    if not cross:
        k_new = dense(xn, p["wk"], p.get("bk")).reshape(b, hkv, hd)
        v_new = dense(xn, p["wv"], p.get("bv")).reshape(b, hkv, hd)
        cos, sin = rope_freqs(cfg, pos[None])           # (1, hd/2)
        q = apply_rope(q.reshape(b, 1, h, hd), cos, sin).reshape(b, h, hd)
        k_new = apply_rope(
            k_new.reshape(b, 1, hkv, hd), cos, sin
        ).reshape(b, hkv, hd)
        cache_k = jax.lax.dynamic_update_slice_in_dim(
            cache_k, k_new[:, None], pos, axis=1
        )
        cache_v = jax.lax.dynamic_update_slice_in_dim(
            cache_v, v_new[:, None], pos, axis=1
        )
        cache_len = pos + 1
    else:
        cache_len = cross_len if cross_len is not None else cache_k.shape[1]
    o = ops.attention_decode(
        q, cache_k, cache_v, jnp.asarray(cache_len, jnp.int32), window=window
    )
    o = dense(o.reshape(b, h * hd), p["wo"])
    if "gate" in p:
        o = jnp.tanh(p["gate"]) * o
    return x + o, cache_k, cache_v


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def init_mlp(cfg: ArchConfig, rng, d_ff: Optional[int] = None) -> Params:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(rng, 3)
    dt = cfg.dtype_()
    s_in, s_out = 1.0 / np.sqrt(d), 1.0 / np.sqrt(ff)
    return {
        "wg": (jax.random.normal(ks[0], (d, ff)) * s_in).astype(dt),
        "wi": (jax.random.normal(ks[1], (d, ff)) * s_in).astype(dt),
        "wo": (jax.random.normal(ks[2], (ff, d)) * s_out).astype(dt),
        "ln": jnp.ones((d,), dt),
    }


def mlp_block(cfg: ArchConfig, p: Params, x: jax.Array) -> jax.Array:
    xn = norm(cfg, p["ln"], x)
    xn = shard(xn, ("data", None, None))   # SP gather-once (see attention)
    h = jax.nn.silu(dense(xn, p["wg"])) * dense(xn, p["wi"])
    h = shard(h, ("data", None, "model"))
    return x + dense(h, p["wo"])


# ---------------------------------------------------------------------------
# MoE (top-k routing, capacity-based dispatch; EP-shardable)
# ---------------------------------------------------------------------------

def init_moe(cfg: ArchConfig, rng) -> Params:
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(rng, 4)
    dt = cfg.dtype_()
    s_in, s_out = 1.0 / np.sqrt(d), 1.0 / np.sqrt(ff)
    return {
        "router": (jax.random.normal(ks[0], (d, e)) * s_in).astype(jnp.float32),
        "wg": (jax.random.normal(ks[1], (e, d, ff)) * s_in).astype(dt),
        "wi": (jax.random.normal(ks[2], (e, d, ff)) * s_in).astype(dt),
        "wo": (jax.random.normal(ks[3], (e, ff, d)) * s_out).astype(dt),
        "ln": jnp.ones((d,), dt),
    }


def moe_block(cfg: ArchConfig, p: Params, x: jax.Array) -> jax.Array:
    """Token-drop capacity MoE, GShard *grouped* formulation.

    Tokens are split into G groups (= data shards) with group-LOCAL
    capacity, so dispatch/scatter is local to each data shard; expert
    tensors carry the E axis for EP over the model axis (or ff-TP when E
    doesn't divide it).  This replaced a global-capacity scatter that
    GSPMD lowered to replicated 5.4 GB buffers per layer — perf iteration
    M1, §Perf (~an order of magnitude of collective traffic).
    """
    from repro.distributed.sharding import axis_size

    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    g = axis_size("data")
    if t % g != 0:
        g = 1
    tl = t // g
    xn = norm(cfg, p["ln"], x).reshape(g, tl, d)
    xn = shard(xn, ("data", None, None))
    logits = jnp.einsum(
        "gtd,de->gte", xn.astype(jnp.float32), p["router"],
        preferred_element_type=jnp.float32,
    )
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)               # (g, tl, k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    cap = max(1, int(np.ceil(tl * k / e * cfg.capacity_factor)))

    def dispatch_group(xg, idxg):
        flat_e = idxg.reshape(tl * k)
        onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
        ranks = jnp.cumsum(onehot, axis=0) - onehot
        rank = jnp.take_along_axis(ranks, flat_e[:, None], axis=1)[:, 0]
        keep = rank < cap
        rank_c = jnp.minimum(rank, cap - 1)
        tok = jnp.arange(tl * k) // k
        buf = jnp.zeros((e, cap, d), xg.dtype)
        buf = buf.at[flat_e, rank_c].add(
            xg[tok] * keep[:, None].astype(xg.dtype)
        )
        return buf, flat_e, rank_c, keep

    buf, flat_e, rank_c, keep = jax.vmap(dispatch_group)(xn, idx)
    # dispatch mirror of M4: build the buffer d-sharded (scatter + its
    # backward gather stay local), THEN all-to-all into the EP layout
    # (perf iteration M5, §Perf)
    buf = shard(buf, ("data", None, None, "model"))
    buf = shard(buf, ("data", "model", None, None))    # G x E (EP)
    h = jax.nn.silu(
        jnp.einsum("gecd,edf->gecf", buf, p["wg"],
                   preferred_element_type=jnp.float32).astype(xn.dtype)
    ) * jnp.einsum("gecd,edf->gecf", buf, p["wi"],
                   preferred_element_type=jnp.float32).astype(xn.dtype)
    h = shard(h, ("data", "model", None, None))
    out_e = jnp.einsum("gecf,efd->gecd", h, p["wo"],
                       preferred_element_type=jnp.float32).astype(xn.dtype)
    # combine: reshard E-sharded -> d-sharded (all-to-all, ~payload/TP per
    # device) so the (e,c) gather below is LOCAL.  Gathering across the
    # model-sharded E dim made GSPMD emit masked-gathers + full (tl*k, d)
    # f32 all-reduces — 2.4 TB/device/step (perf iteration M4, §Perf).
    out_e = shard(out_e, ("data", None, None, "model"))
    pulled = jax.vmap(
        lambda oe, fe, rc, kp: oe[fe, rc] * kp[:, None].astype(oe.dtype)
    )(out_e, flat_e, rank_c, keep)                     # (g, tl*k, d)
    combined = (
        pulled.reshape(g, tl, k, d) * gates[..., None].astype(xn.dtype)
    ).sum(axis=2)
    return x + combined.reshape(b, s, d)


# ---------------------------------------------------------------------------
# Mamba-2 block (conv1d + SSD), train and decode paths
# ---------------------------------------------------------------------------

def init_mamba(cfg: ArchConfig, rng) -> Params:
    d, di = cfg.d_model, cfg.d_inner
    n, h, cd = cfg.ssm_state, cfg.ssm_heads, cfg.conv_dim
    ks = jax.random.split(rng, 4)
    dt = cfg.dtype_()
    s = 1.0 / np.sqrt(d)
    return {
        # in_proj emits [z (di), x (di), B (n), C (n), dt (h)]
        "w_in": (jax.random.normal(ks[0], (d, 2 * di + 2 * n + h)) * s).astype(dt),
        # depthwise causal conv over x|B|C, with a bias
        "conv_w": (jax.random.normal(ks[1], (cfg.ssm_conv, cd)) * 0.1).astype(dt),
        "conv_b": jnp.zeros((cd,), dt),
        "a_log": jnp.zeros((h,), jnp.float32),
        "d_skip": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "w_out": (jax.random.normal(ks[2], (di, d)) / np.sqrt(di)).astype(dt),
        "ln": jnp.ones((d,), dt),
        "ln_inner": jnp.ones((di,), dt),
    }


def _split_mamba_proj(cfg: ArchConfig, zxbcdt: jax.Array):
    """``z``, the conv's input ``xBC`` and ``dt`` of the in-projection."""
    di = cfg.d_inner
    return (zxbcdt[..., :di], zxbcdt[..., di : di + cfg.conv_dim],
            zxbcdt[..., di + cfg.conv_dim :])


def _conv_silu(cfg: ArchConfig, p: Params, win: jax.Array, c: int):
    """Causal depthwise conv of the last ``c`` positions of ``win`` (B,
    K-1+c, conv_dim), each reading its K-1 predecessors, with the bias,
    then SiLU (in f32); split into x (B, c, di), B and C (B, c, n) in the
    served dtype."""
    k, di, n = cfg.ssm_conv, cfg.d_inner, cfg.ssm_state
    w = p["conv_w"].astype(jnp.float32)
    out = p["conv_b"].astype(jnp.float32) + sum(
        win[:, i : i + c].astype(jnp.float32) * w[i] for i in range(k)
    )
    xbc = jax.nn.silu(out).astype(cfg.dtype_())
    return xbc[..., :di], xbc[..., di : di + n], xbc[..., di + n :]


def _mamba_out(cfg: ArchConfig, p: Params, x, y, xs, z):
    """D skip, gate, gated RMSNorm (in f32), out-projection, residual.
    ``y``/``xs``: (B, S, H, P); ``z``: (B, S, di); ``x``: the residual."""
    b, s = y.shape[:2]
    y = y + xs * p["d_skip"][None, None, :, None]
    y = y.reshape(b, s, cfg.d_inner) * jax.nn.silu(z.astype(jnp.float32))
    y = ops.rmsnorm(y, p["ln_inner"], cfg.norm_eps).astype(cfg.dtype_())
    return x + dense(y, p["w_out"])


def mamba_block(cfg: ArchConfig, p: Params, x: jax.Array) -> jax.Array:
    """Mamba-2 block over whole sequences (training): ``x`` (B, S, d) is
    the residual, in f32 where ``cfg.residual_f32``."""
    b, s, d = x.shape
    h, hd, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    xn = norm(cfg, p["ln"], x).astype(cfg.dtype_())
    z, xbc, dt = _split_mamba_proj(cfg, dense(xn, p["w_in"]))
    win = jnp.pad(xbc, ((0, 0), (cfg.ssm_conv - 1, 0), (0, 0)))
    xs, b_, c_ = _conv_silu(cfg, p, win, s)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    a = -jnp.exp(p["a_log"])
    xs = xs.reshape(b, s, h, hd)
    y = ops.ssd_scan(
        xs, dt, a, b_.reshape(b, s, 1, n), c_.reshape(b, s, 1, n),
        chunk=cfg.ssm_chunk,
    )
    return _mamba_out(cfg, p, x, y, xs, z)


def mamba_prefill_block(
    cfg: ArchConfig, p: Params,
    x: jax.Array,              # (B, C, d): C tokens per sequence
    ssm_state: jax.Array,      # (B, H, P, N) f32: carried recurrent state
    conv_state: jax.Array,     # (B, K-1, conv_dim): carried conv window
    valid: jax.Array,          # (B, C) bool: prefix mask of real tokens
):
    """Chunked Mamba-2 block with carried recurrent state — the single
    source of the recurrent families' serving-time math.

    A chunk of C tokens runs as B*C-row projections, one chunked causal
    conv of x|B|C against the carried (K-1)-deep window, and one SSD scan
    seeded with the carried state (``ops.ssd_prefill_chunk``) — instead of
    C sequential single-token dispatches.  ``mamba_decode_block`` is the
    C=1 case of this function, so decode and prefill share one
    accumulation order rather than two hand-synchronized recurrences.
    ``x`` is the residual stream (f32 where ``cfg.residual_f32``): the
    block normalizes it, projects in the served dtype and adds its output
    back.

    Per-row widths ride on the ``valid`` prefix mask: a padding position's
    ``dt`` is zeroed (exp(0) decay, zero input — an algebraic no-op on the
    SSD state), and the new conv window is gathered to end at each row's
    last *real* token, so neither carry ever sees padding.  Rows with no
    real tokens (``valid`` all False) carry both states through untouched.
    Outputs at padding positions are finite garbage the caller discards.
    """
    b, c, d = x.shape
    h, hd, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    k = cfg.ssm_conv
    xn = norm(cfg, p["ln"], x).astype(cfg.dtype_())
    z, xbc, dt = _split_mamba_proj(cfg, dense(xn, p["w_in"]))
    # chunked causal conv against the carried window: position i of the
    # chunk reads raw inputs i-K+1..i, reaching into the carry for i < K-1
    win = jnp.concatenate([conv_state, xbc], axis=1)   # (B, K-1+C, conv_dim)
    xs, b_, c_ = _conv_silu(cfg, p, win, c)
    # new conv window: the last K-1 inputs up to each row's width (width 0
    # gathers win[:, :K-1] — the old carry, verbatim)
    width = jnp.sum(valid, axis=1, dtype=jnp.int32)        # (B,)
    gidx = width[:, None] + jnp.arange(k - 1, dtype=jnp.int32)[None, :]
    conv_state = jnp.take_along_axis(win, gidx[:, :, None], axis=1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    dt = jnp.where(valid[:, :, None], dt, 0.0)             # padding: no-op
    a = -jnp.exp(p["a_log"])
    xs = xs.reshape(b, c, h, hd)
    y, ssm_state = ops.ssd_prefill_chunk(
        xs, dt, a, b_.reshape(b, c, 1, n), c_.reshape(b, c, 1, n),
        ssm_state, chunk=cfg.ssm_chunk,
    )
    return _mamba_out(cfg, p, x, y, xs, z), ssm_state, conv_state


def mamba_decode_block(
    cfg: ArchConfig, p: Params, x: jax.Array,
    ssm_state: jax.Array,      # (B, H, P, N)
    conv_state: jax.Array,     # (B, K-1, conv_dim)
    valid: Optional[jax.Array] = None,   # (B, 1) bool; None = all rows live
):
    """Single-token decode — the C=1 case of ``mamba_prefill_block``.

    One code path serves both regimes; the sequential recurrence is the
    chunked scan's degenerate case, not a second implementation kept in
    parity by hand (the dispatch layer may still pick a cheaper lowering
    for S=1 — specialization stays below this line).

    ``valid`` is the per-row liveness mask: a False row carries both
    recurrent states through untouched (the width-0 no-op documented on
    ``mamba_prefill_block``).  The serving engine relies on this for
    preempted/spilled rows, whose live state must survive in place while
    the lane idles; ``None`` keeps the historical all-rows-live default.
    """
    if valid is None:
        valid = jnp.ones((x.shape[0], 1), bool)
    y, ssm_state, conv_state = mamba_prefill_block(
        cfg, p, x[:, None], ssm_state, conv_state, valid
    )
    return y[:, 0], ssm_state, conv_state
