"""Portable ops — the public, differentiable, backend-switched operator set.

Every op here is registered once in ``repro.core.registry`` with its two
lowerings and exposed as a plain function.  Model code (the Caffe port, the
LM zoo) calls *these*; whether a Pallas kernel or the jnp oracle runs is
decided by the policy switch — the paper's single-source property.

Differentiation strategy mirrors the paper's porting strategy:
  * REFERENCE backend: the jnp oracle is used directly (autodiff-able).
  * PALLAS backend: a ``jax.custom_vjp`` pairs the forward kernel with its
    hand-written backward kernel(s); ops whose backward is not yet ported
    (ssd_scan) fall back to the oracle's vjp — recorded in ``coverage()``
    exactly like the paper's Table 1 records partially-ported blocks.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.policy import Backend, current_backend
from repro.core.registry import get_tuning, register_op
from repro.tuning.shapes import shape_class
from repro.kernels import ref
from repro.kernels.eltwise import (
    bias_add_rows_pallas,
    relu_bwd_pallas,
    relu_pallas,
)
from repro.kernels.flash_attention import (
    flash_attention_bwd_pallas,
    flash_attention_pallas,
    flash_decode_paged_pallas,
    flash_decode_paged_quant_pallas,
    flash_decode_pallas,
    flash_prefill_chunk_paged_pallas,
    flash_prefill_chunk_paged_quant_pallas,
    flash_prefill_chunk_pallas,
)
from repro.kernels.gemm import gemm_pallas
from repro.kernels.im2col import col2im_pallas, im2col_pallas
from repro.kernels.mamba_scan import ssd_scan_pallas
from repro.kernels.pooling import maxpool_bwd_pallas, maxpool_pallas
from repro.kernels.rmsnorm import rmsnorm_bwd_pallas, rmsnorm_pallas
from repro.kernels.softmax_xent import (
    softmax_pallas,
    softmax_xent_bwd_pallas,
    softmax_xent_pallas,
)


def _pallas() -> bool:
    return current_backend() is Backend.PALLAS


# ---------------------------------------------------------------------------
# matmul  (InnerProduct / projections)
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _matmul_p(a, b):
    return gemm_pallas(a, b)


def _matmul_p_fwd(a, b):
    return gemm_pallas(a, b), (a, b)


def _matmul_p_bwd(res, g):
    a, b = res
    da = gemm_pallas(g, b.T, out_dtype=a.dtype)
    db = gemm_pallas(a.T, g, out_dtype=b.dtype)
    return da, db


_matmul_p.defvjp(_matmul_p_fwd, _matmul_p_bwd)


@jax.custom_vjp
def _matmul_r(a, b):
    return ref.gemm(a, b)


def _matmul_r_fwd(a, b):
    return ref.gemm(a, b), (a, b)


def _matmul_r_bwd(res, g):
    # Mixed-precision backward: f32 MXU accumulation but cotangent WIRES in
    # the param dtype.  Without this, the vjp of dot(..., pet=f32).astype
    # produces f32 cotangents that flow through the whole backward graph,
    # doubling collective + HBM traffic (perf iteration L2, §Perf).
    a, b = res
    g = g.astype(a.dtype)
    da = ref.gemm(g, b.T, out_dtype=a.dtype)
    db = ref.gemm(a.T, g, out_dtype=b.dtype)
    return da, db


_matmul_r.defvjp(_matmul_r_fwd, _matmul_r_bwd)


def matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """(M,K) @ (K,N), f32 accumulation, param-dtype cotangents."""
    return _matmul_p(a, b) if _pallas() else _matmul_r(a, b)


# ---------------------------------------------------------------------------
# bias add over rows (the paper's matrixPlusVectorRows)
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _bias_rows_p(m, v):
    return bias_add_rows_pallas(m, v)


def _bias_rows_p_fwd(m, v):
    return bias_add_rows_pallas(m, v), None


def _bias_rows_p_bwd(_, g):
    return g, g.sum(axis=0)


_bias_rows_p.defvjp(_bias_rows_p_fwd, _bias_rows_p_bwd)


def bias_add_rows(m: jax.Array, v: jax.Array) -> jax.Array:
    return _bias_rows_p(m, v) if _pallas() else ref.bias_add_rows(m, v)


# ---------------------------------------------------------------------------
# relu (Caffe's leaky-capable ReLU)
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _relu_p(x, slope):
    return relu_pallas(x, slope)


def _relu_p_fwd(x, slope):
    return relu_pallas(x, slope), x


def _relu_p_bwd(slope, x, g):
    return (relu_bwd_pallas(x, g, slope),)


_relu_p.defvjp(_relu_p_fwd, _relu_p_bwd)


def relu(x: jax.Array, negative_slope: float = 0.0) -> jax.Array:
    return (
        _relu_p(x, negative_slope)
        if _pallas()
        else ref.relu(x, negative_slope)
    )


# ---------------------------------------------------------------------------
# im2col / col2im / conv2d (Caffe's Convolution block)
# ---------------------------------------------------------------------------

def im2col(x, kh, kw, stride=1, pad=0):
    if _pallas():
        return im2col_pallas(x, kh, kw, stride, pad)
    return ref.im2col(x, kh, kw, stride, pad)


def col2im(cols, x_shape, kh, kw, stride=1, pad=0):
    if _pallas() and stride == 1:
        return col2im_pallas(cols, tuple(x_shape), kh, kw, stride, pad)
    return ref.col2im(cols, x_shape, kh, kw, stride, pad)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _conv2d_p(x, w, b, stride, pad, has_bias):
    return _conv2d_fwd_impl(x, w, b, stride, pad, has_bias)


def _conv2d_fwd_impl(x, w, b, stride, pad, has_bias):
    n, c, h, wd = x.shape
    f, _, kh, kw = w.shape
    oh = ref.conv_out_size(h, kh, stride, pad)
    ow = ref.conv_out_size(wd, kw, stride, pad)
    cols = im2col_pallas(x, kh, kw, stride, pad)     # (n, k, o)
    wmat = w.reshape(f, c * kh * kw)
    # batched GEMM via flattening batch into the N dim: (f,k) @ (k, n*o)
    cols2 = cols.transpose(1, 0, 2).reshape(c * kh * kw, n * oh * ow)
    out = gemm_pallas(wmat, cols2)                   # (f, n*o)
    out = out.reshape(f, n, oh * ow).transpose(1, 0, 2)
    if has_bias:
        out = out + b[None, :, None]
    return out.reshape(n, f, oh, ow)


def _conv2d_p_fwd(x, w, b, stride, pad, has_bias):
    return _conv2d_fwd_impl(x, w, b, stride, pad, has_bias), (x, w)


def _conv2d_p_bwd(stride, pad, has_bias, res, dy):
    x, w = res
    n, c, h, wd = x.shape
    f, _, kh, kw = w.shape
    oh, ow = dy.shape[2], dy.shape[3]
    dy2 = dy.reshape(n, f, oh * ow)
    cols = im2col_pallas(x, kh, kw, stride, pad)
    # dW = sum_n dy_n @ cols_n^T  -> single GEMM over concatenated batch
    dy_flat = dy2.transpose(1, 0, 2).reshape(f, n * oh * ow)
    cols_flat = cols.transpose(1, 0, 2).reshape(c * kh * kw, n * oh * ow)
    dwmat = gemm_pallas(dy_flat, cols_flat.T, out_dtype=w.dtype)
    dw = dwmat.reshape(f, c, kh, kw)
    # dX = col2im(W^T @ dy)
    wmat = w.reshape(f, c * kh * kw)
    dcols = gemm_pallas(wmat.T, dy_flat, out_dtype=x.dtype)  # (k, n*o)
    dcols = dcols.reshape(c * kh * kw, n, oh * ow).transpose(1, 0, 2)
    dx = col2im(dcols, x.shape, kh, kw, stride, pad)
    db = dy.sum(axis=(0, 2, 3)) if has_bias else jnp.zeros((f,), dy.dtype)
    return dx, dw, db


_conv2d_p.defvjp(_conv2d_p_fwd, _conv2d_p_bwd)


def conv2d(
    x: jax.Array,
    w: jax.Array,
    b: Optional[jax.Array] = None,
    *,
    stride: int = 1,
    pad: int = 0,
) -> jax.Array:
    if _pallas():
        has_bias = b is not None
        bb = b if has_bias else jnp.zeros((w.shape[0],), x.dtype)
        return _conv2d_p(x, w, bb, stride, pad, has_bias)
    return ref.conv2d(x, w, b, stride=stride, pad=pad)


# ---------------------------------------------------------------------------
# maxpool / avgpool (Caffe's Pooling block)
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _maxpool_arg_p(x, k, stride, pad):
    return maxpool_pallas(x, k, stride, pad)


def _maxpool_arg_p_fwd(x, k, stride, pad):
    out, arg = maxpool_pallas(x, k, stride, pad)
    return (out, arg), (arg, x.shape)


def _maxpool_arg_p_bwd(k, stride, pad, res, g):
    arg, x_shape = res
    dy = g[0]  # argmax cotangent is float0
    if stride >= k:
        return (maxpool_bwd_pallas(dy, arg, x_shape, k, stride, pad),)
    return (ref.maxpool_bwd(dy, arg, x_shape, k, stride, pad),)


_maxpool_arg_p.defvjp(_maxpool_arg_p_fwd, _maxpool_arg_p_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _maxpool_arg_r(x, k, stride, pad):
    return ref.maxpool(x, k, stride, pad)


def _maxpool_arg_r_fwd(x, k, stride, pad):
    out, arg = ref.maxpool(x, k, stride, pad)
    return (out, arg), (arg, x.shape)


def _maxpool_arg_r_bwd(k, stride, pad, res, g):
    arg, x_shape = res
    return (ref.maxpool_bwd(g[0], arg, x_shape, k, stride, pad),)


_maxpool_arg_r.defvjp(_maxpool_arg_r_fwd, _maxpool_arg_r_bwd)


def maxpool_with_argmax(x: jax.Array, k: int, stride: int, pad: int = 0):
    """One pool evaluation returning ``(out, argmax)``.

    For callers that keep the argmax themselves (the Caffe Pooling layer
    stores the mapping for its explicit backward).  Running ``maxpool`` and
    then the oracle again just for the argmax would double the hot-path cost
    and could disagree on ties across backends; this dispatches once and
    returns both from the same kernel.  Differentiable in ``out``.
    """
    if _pallas():
        return _maxpool_arg_p(x, k, stride, pad)
    return _maxpool_arg_r(x, k, stride, pad)


def maxpool(x: jax.Array, k: int, stride: int, pad: int = 0) -> jax.Array:
    return maxpool_with_argmax(x, k, stride, pad)[0]


def avgpool(x: jax.Array, k: int, stride: int, pad: int = 0) -> jax.Array:
    return ref.avgpool(x, k, stride, pad)


# ---------------------------------------------------------------------------
# softmax / softmax-xent (Caffe's SoftMax / SoftMaxWithLoss)
# ---------------------------------------------------------------------------

def softmax(x: jax.Array, axis: int = -1) -> jax.Array:
    if _pallas() and axis in (-1, x.ndim - 1):
        return softmax_pallas(x)
    return ref.softmax(x, axis)


@jax.custom_vjp
def _xent_p(logits, labels):
    loss, _ = softmax_xent_pallas(logits, labels)
    return loss


def _xent_p_fwd(logits, labels):
    loss, probs = softmax_xent_pallas(logits, labels)
    return loss, (probs, labels)


def _xent_p_bwd(res, g):
    probs, labels = res
    return softmax_xent_bwd_pallas(probs, labels) * g, None


_xent_p.defvjp(_xent_p_fwd, _xent_p_bwd)


@jax.custom_vjp
def _xent_r(logits, labels):
    loss, _ = ref.softmax_xent(logits, labels)
    return loss


def _xent_r_fwd(logits, labels):
    loss, probs = ref.softmax_xent(logits, labels)
    return loss, (probs, labels)


def _xent_r_bwd(res, g):
    probs, labels = res
    return ref.softmax_xent_bwd(probs, labels) * g, None


_xent_r.defvjp(_xent_r_fwd, _xent_r_bwd)


def softmax_xent_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean NLL over rows; labels int32 (B,). Fused fwd+analytic bwd."""
    if _pallas():
        return _xent_p(logits, labels)
    return _xent_r(logits, labels)


def accuracy(logits: jax.Array, labels: jax.Array, top_k: int = 1) -> jax.Array:
    return ref.accuracy(logits, labels, top_k)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rmsnorm_p(x, w, eps):
    return rmsnorm_pallas(x, w, eps)


def _rmsnorm_p_fwd(x, w, eps):
    return rmsnorm_pallas(x, w, eps), (x, w)


def _rmsnorm_p_bwd(eps, res, g):
    x, w = res
    return rmsnorm_bwd_pallas(x, w, g, eps)


_rmsnorm_p.defvjp(_rmsnorm_p_fwd, _rmsnorm_p_bwd)


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    return _rmsnorm_p(x, w, eps) if _pallas() else ref.rmsnorm(x, w, eps)


def layernorm(x, w, b, eps: float = 1e-5):
    return ref.layernorm(x, w, b, eps)


# ---------------------------------------------------------------------------
# attention (flash) — custom_vjp pairs the fwd/bwd kernels
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _attn_p(q, k, v, causal, window, scale):
    out, _ = flash_attention_pallas(
        q, k, v, causal=causal, window=window, scale=scale
    )
    return out


def _attn_p_fwd(q, k, v, causal, window, scale):
    out, lse = flash_attention_pallas(
        q, k, v, causal=causal, window=window, scale=scale
    )
    return out, (q, k, v, out, lse)


def _attn_p_bwd(causal, window, scale, res, do):
    q, k, v, out, lse = res
    return flash_attention_bwd_pallas(
        q, k, v, out, lse, do, causal=causal, window=window, scale=scale
    )


_attn_p.defvjp(_attn_p_fwd, _attn_p_bwd)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """GQA attention (B,Sq,Hq,D)x(B,Sk,Hkv,D) -> (B,Sq,Hq,D)."""
    if _pallas():
        return _attn_p(q, k, v, causal, window, scale)
    return ref.mha_attention(q, k, v, causal=causal, window=window, scale=scale)


def _attention_decode_ref(q, k_cache, v_cache, cache_len, *,
                          window=None, scale=None):
    """jnp oracle: one query row per sequence against a (B,Smax,Hkv,D) cache."""
    b, hq, d = q.shape
    smax = k_cache.shape[1]
    # per-row valid lengths (continuous batching: rows at different depths)
    lens = jnp.broadcast_to(
        jnp.asarray(cache_len, jnp.int32).reshape(-1), (b,)
    )
    kpos = jnp.arange(smax)
    mask = kpos[None, :] < lens[:, None]                    # (B, Smax)
    if window is not None:
        mask &= kpos[None, :] >= lens[:, None] - window
    hkv = k_cache.shape[2]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, d)
    s = jnp.einsum(
        "bhgd,bshd->bhgs", qg, k_cache, preferred_element_type=jnp.float32
    ) * (scale if scale is not None else 1.0 / jnp.sqrt(d).astype(jnp.float32))
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p.astype(v_cache.dtype), v_cache)
    return o.reshape(b, hq, d)


def _attention_decode_paged_ref(q, k_pages, v_pages, cache_len, block_table,
                                *, window=None, scale=None):
    """Paged oracle: gather each row's pages into a logical (B,S,Hkv,D)
    cache, then run the dense math.  Unmapped blocks (-1) gather page 0;
    their garbage keys sit at ``kpos >= cache_len`` and are masked."""
    b = q.shape[0]
    n_pages, page, hkv, d = k_pages.shape
    bt = jnp.clip(block_table, 0, n_pages - 1)
    k = k_pages[bt].reshape(b, -1, hkv, d)       # (B, max_blocks*page, ...)
    v = v_pages[bt].reshape(b, -1, hkv, d)
    return _attention_decode_ref(q, k, v, cache_len, window=window,
                                 scale=scale)


def _attention_decode_paged_quant_ref(q, k_pages, v_pages, k_scale, v_scale,
                                      cache_len, block_table, *,
                                      window=None, scale=None):
    """Quantized paged oracle: dequantize the int8 pool with its
    per-(page, head) scales (f32, R007), then delegate to the paged
    oracle — one dequant definition the Pallas kernel is held to."""
    kf = k_pages.astype(jnp.float32) * k_scale[:, None, :, None]
    vf = v_pages.astype(jnp.float32) * v_scale[:, None, :, None]
    return _attention_decode_paged_ref(q, kf, vf, cache_len, block_table,
                                       window=window, scale=scale)


def attention_decode(
    q: jax.Array,          # (B, Hq, D)
    k_cache: jax.Array,    # contiguous: (B, Smax, Hkv, D);
                           # paged: (n_pages, page_size, Hkv, D) page pool
    v_cache: jax.Array,
    cache_len: jax.Array,  # int32 () or (B,): valid prefix incl. current token
    *,
    block_table: Optional[jax.Array] = None,   # (B, max_blocks) int32, paged
    kv_scales=None,        # (ksc, vsc) (n_pages, Hkv) f32: int8 pool scales
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Single-token decode attention over a KV cache.

    The cache layout is the ``KVCacheLayout`` switch point: with
    ``block_table=None`` the caches are the contiguous per-row slab; with a
    block table they are a shared page pool (``repro.serving.pager``
    documents the contract).  ``kv_scales`` (paged only) marks the pool as
    per-(page, head)-scaled int8 and routes to the quantized lowerings,
    which dequantize in-kernel.  Every layout x dtype cell has a reference
    and a Pallas lowering kept in lock-step.
    """
    if block_table is not None:
        if kv_scales is not None:
            ksc, vsc = kv_scales
            if _pallas():
                return flash_decode_paged_quant_pallas(
                    q, k_cache, v_cache, ksc, vsc, cache_len, block_table,
                    window=window, scale=scale,
                )
            return _attention_decode_paged_quant_ref(
                q, k_cache, v_cache, ksc, vsc, cache_len, block_table,
                window=window, scale=scale,
            )
        if _pallas():
            return flash_decode_paged_pallas(
                q, k_cache, v_cache, cache_len, block_table,
                window=window, scale=scale,
            )
        return _attention_decode_paged_ref(
            q, k_cache, v_cache, cache_len, block_table,
            window=window, scale=scale,
        )
    if kv_scales is not None:
        raise ValueError(
            "kv_scales needs the paged layout (block_table) — the "
            "contiguous slab is never quantized"
        )
    if _pallas():
        return flash_decode_pallas(
            q, k_cache, v_cache, cache_len, window=window, scale=scale
        )
    return _attention_decode_ref(q, k_cache, v_cache, cache_len,
                                 window=window, scale=scale)


def _attention_prefill_chunk_ref(q, k_cache, v_cache, start, width, *,
                                 window=None, scale=None):
    """jnp oracle: C query rows per sequence vs a (B,Smax,Hkv,D) cache.

    Query i of row b sits at absolute position ``start[b] + i`` and sees
    keys at ``kpos <= start[b] + i`` (window-limited when set).  Padding
    rows (``i >= width[b]``) alias the last real position so every softmax
    row keeps at least one finite score — garbage-but-finite outputs the
    caller discards (a NaN would leak into real tokens via MoE dispatch).
    """
    b, c, hq, d = q.shape
    smax, hkv = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    starts = jnp.broadcast_to(
        jnp.asarray(start, jnp.int32).reshape(-1), (b,)
    )
    widths = jnp.broadcast_to(
        jnp.asarray(width, jnp.int32).reshape(-1), (b,)
    )
    i = jnp.arange(c, dtype=jnp.int32)[None, :]
    qpos = starts[:, None] + jnp.minimum(i, widths[:, None] - 1)  # (B, C)
    kpos = jnp.arange(smax)
    mask = kpos[None, None, :] <= qpos[:, :, None]                # (B, C, S)
    if window is not None:
        mask &= kpos[None, None, :] > qpos[:, :, None] - window
    qg = q.reshape(b, c, hkv, g, d)
    s = jnp.einsum(
        "bchgd,bshd->bchgs", qg, k_cache, preferred_element_type=jnp.float32
    ) * (scale if scale is not None else 1.0 / jnp.sqrt(d).astype(jnp.float32))
    s = jnp.where(mask[:, :, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bchgs,bshd->bchgd", p.astype(v_cache.dtype), v_cache)
    return o.reshape(b, c, hq, d)


def _attention_prefill_chunk_paged_ref(q, k_pages, v_pages, start, width,
                                       block_table, *, window=None,
                                       scale=None):
    """Paged oracle: gather each row's pages into a logical cache, then run
    the dense chunk math.  Unmapped blocks (-1) gather page 0; their
    garbage keys sit past ``start + width - 1`` and are masked."""
    b = q.shape[0]
    n_pages, page, hkv, d = k_pages.shape
    bt = jnp.clip(block_table, 0, n_pages - 1)
    k = k_pages[bt].reshape(b, -1, hkv, d)
    v = v_pages[bt].reshape(b, -1, hkv, d)
    return _attention_prefill_chunk_ref(q, k, v, start, width,
                                        window=window, scale=scale)


def _attention_prefill_chunk_paged_quant_ref(q, k_pages, v_pages, k_scale,
                                             v_scale, start, width,
                                             block_table, *, window=None,
                                             scale=None):
    """Quantized paged chunk oracle: dequantize (f32, R007), then run the
    paged oracle — same single dequant definition as the decode path."""
    kf = k_pages.astype(jnp.float32) * k_scale[:, None, :, None]
    vf = v_pages.astype(jnp.float32) * v_scale[:, None, :, None]
    return _attention_prefill_chunk_paged_ref(q, kf, vf, start, width,
                                              block_table, window=window,
                                              scale=scale)


def attention_prefill_chunk(
    q: jax.Array,          # (B, C, Hq, D): C prompt tokens per sequence
    k_cache: jax.Array,    # contiguous: (B, Smax, Hkv, D);
                           # paged: (n_pages, page_size, Hkv, D) page pool
    v_cache: jax.Array,
    start: jax.Array,      # int32 () or (B,): absolute pos of chunk token 0
    width: jax.Array,      # int32 () or (B,): real tokens in the chunk
    *,
    block_table: Optional[jax.Array] = None,   # (B, max_blocks) int32, paged
    kv_scales=None,        # (ksc, vsc) (n_pages, Hkv) f32: int8 pool scales
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Chunked-prefill attention over a KV cache.

    The multi-token sibling of ``attention_decode`` and the same
    ``KVCacheLayout`` switch point: ``block_table=None`` selects the
    contiguous per-row slab, a block table selects the shared page pool
    (contract in ``repro.serving.pager``); ``kv_scales`` routes the paged
    pool through the quantized lowerings (in-kernel dequant).  The chunk's
    own K/V must be in the cache already; causality inside the chunk is
    pure masking.  Every cell has a reference and a Pallas lowering kept
    in lock-step.
    """
    if block_table is not None:
        if kv_scales is not None:
            ksc, vsc = kv_scales
            if _pallas():
                return flash_prefill_chunk_paged_quant_pallas(
                    q, k_cache, v_cache, ksc, vsc, start, width,
                    block_table, window=window, scale=scale,
                )
            return _attention_prefill_chunk_paged_quant_ref(
                q, k_cache, v_cache, ksc, vsc, start, width, block_table,
                window=window, scale=scale,
            )
        if _pallas():
            return flash_prefill_chunk_paged_pallas(
                q, k_cache, v_cache, start, width, block_table,
                window=window, scale=scale,
            )
        return _attention_prefill_chunk_paged_ref(
            q, k_cache, v_cache, start, width, block_table,
            window=window, scale=scale,
        )
    if kv_scales is not None:
        raise ValueError(
            "kv_scales needs the paged layout (block_table) — the "
            "contiguous slab is never quantized"
        )
    if _pallas():
        return flash_prefill_chunk_pallas(
            q, k_cache, v_cache, start, width, window=window, scale=scale
        )
    return _attention_prefill_chunk_ref(q, k_cache, v_cache, start, width,
                                        window=window, scale=scale)


# ---------------------------------------------------------------------------
# Mamba-2 SSD scan — pallas fwd; bwd falls back to oracle vjp (recorded)
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ssd_p(x, dt, A, B_, C, chunk):
    y, _ = ssd_scan_pallas(x, dt, A, B_, C, chunk=chunk)
    return y


def _ssd_p_fwd(x, dt, A, B_, C, chunk):
    y, _ = ssd_scan_pallas(x, dt, A, B_, C, chunk=chunk)
    return y, (x, dt, A, B_, C)


def _ssd_p_bwd(chunk, res, dy):
    x, dt, A, B_, C = res
    # backward not yet ported to Pallas: oracle vjp (paper-style partial port)
    _, vjp = jax.vjp(
        lambda *args: ref.ssd_scan(*args, chunk=chunk)[0], x, dt, A, B_, C
    )
    return vjp(dy)


_ssd_p.defvjp(_ssd_p_fwd, _ssd_p_bwd)


def ssd_scan(
    x, dt, A, B_, C, *, chunk: int = 64, initial_state=None, return_state=False
):
    """Mamba-2 SSD. B_/C: (B,S,G,N). The Pallas path raises unless G==1."""
    if return_state or initial_state is not None:
        # stateful path (serving): no grad needed; direct dispatch
        if _pallas():
            return ssd_scan_pallas(
                x, dt, A, B_, C, chunk=chunk, initial_state=initial_state
            )
        return ref.ssd_scan(
            x, dt, A, B_, C, chunk=chunk, initial_state=initial_state
        )
    if _pallas():
        return _ssd_p(x, dt, A, B_, C, chunk)
    return ref.ssd_scan(x, dt, A, B_, C, chunk=chunk)[0]


def ssd_prefill_chunk(
    x: jax.Array,      # (B, C, H, P): C tokens per sequence
    dt: jax.Array,     # (B, C, H) f32; dt == 0 marks padding (state no-op)
    A: jax.Array,      # (H,)
    B_: jax.Array,     # (B, C, G, N)
    C: jax.Array,      # (B, C, G, N)
    state: jax.Array,  # (B, H, P, N) f32: carried recurrent state
    *,
    chunk: int = 64,
) -> tuple:
    """Chunked-SSD serving scan: C tokens against a carried recurrent state.

    The recurrent sibling of ``attention_prefill_chunk`` and the single
    dispatch point for every serving-time SSD recurrence: chunked prefill
    ingests whole token chunks through one scan (B*C-row GEMMs instead of
    C sequential dispatches), and single-token decode is the same call at
    C == 1 — the degenerate case of the chunked formulation, so prefill
    and decode share one accumulation order instead of maintaining two
    recurrences in parity by hand.  Per-row widths are expressed by
    zeroing ``dt`` at padding positions (exp(0) decay, zero input — an
    algebraic state no-op; see ``ref.ssd_scan``).  Returns
    ``(y (B,C,H,P), new_state (B,H,P,N) f32)``.

    The SSD chunk size is a tuning parameter (``get_tuning(
    "ssd_prefill_chunk")``), clamped to the token count so short chunks —
    and the C=1 decode case — never pad to a full training-size chunk.
    Both lowerings are registered and kept in lock-step
    (``ssd_prefill_chunk`` in ``coverage()``).
    """
    t = get_tuning("ssd_prefill_chunk", key=shape_class(s=x.shape[1]),
                   chunk=chunk)
    c = max(1, min(int(t["chunk"]), x.shape[1]))
    if _pallas():
        # the kernel re-resolves its chunk from the tuning table; naming
        # this op's entry keeps the serving knob authoritative (idempotent
        # second lookup) instead of letting "ssd_scan" training tuning
        # override it
        return ssd_scan_pallas(x, dt, A, B_, C, chunk=c,
                               initial_state=state,
                               tuning_op="ssd_prefill_chunk")
    return ref.ssd_scan(x, dt, A, B_, C, chunk=c, initial_state=state)


# ---------------------------------------------------------------------------
# Registry entries (introspection / coverage reporting, Table-1 analogue)
# ---------------------------------------------------------------------------

register_op("matmul", reference=ref.gemm, pallas=gemm_pallas,
            doc="MXU-tiled GEMM", tuning="gemm")
register_op("bias_add_rows", reference=ref.bias_add_rows,
            pallas=bias_add_rows_pallas, doc="matrixPlusVectorRows functor",
            tuning="bias_add")
register_op("relu", reference=ref.relu, pallas=relu_pallas,
            doc="leaky-capable ReLU", tuning="relu")
register_op("im2col", reference=ref.im2col, pallas=im2col_pallas,
            doc="merged penta-loop im2col", tuning=())
register_op("col2im", reference=ref.col2im, pallas=col2im_pallas,
            doc="gather-form col2im (stride=1)", tuning=())
register_op("conv2d", reference=ref.conv2d, pallas=_conv2d_fwd_impl,
            doc="im2col+GEMM convolution", tuning="gemm")
from repro.kernels.conv_direct import conv2d_direct_pallas  # noqa: E402
register_op("conv2d_direct", reference=ref.conv2d,
            pallas=conv2d_direct_pallas,
            doc="fused direct conv (implicit GEMM; beyond-paper)",
            tuning="conv_direct")
register_op("maxpool", reference=ref.maxpool, pallas=maxpool_pallas,
            doc="argmax-tracking maxpool", tuning=())
register_op("avgpool", reference=ref.avgpool, pallas=None,
            doc="average pool (reference only)", reference_only=True)
register_op("softmax", reference=ref.softmax, pallas=softmax_pallas,
            doc="row softmax", tuning="softmax")
register_op("softmax_xent", reference=ref.softmax_xent,
            pallas=softmax_xent_pallas, doc="fused softmax+NLL",
            tuning="softmax_xent")
register_op("accuracy", reference=ref.accuracy, pallas=None,
            doc="top-k accuracy (reference only)", reference_only=True)
register_op("rmsnorm", reference=ref.rmsnorm, pallas=rmsnorm_pallas,
            doc="fused RMSNorm", tuning="rmsnorm")
register_op("layernorm", reference=ref.layernorm, pallas=None,
            doc="LayerNorm (reference only)", reference_only=True)
register_op("attention", reference=ref.mha_attention,
            pallas=flash_attention_pallas, doc="GQA flash attention",
            tuning="flash_attention")
register_op("attention_decode", reference=ref.mha_attention,
            pallas=flash_decode_pallas, doc="KV-cache decode attention",
            tuning="flash_decode")
register_op("attention_decode_paged", reference=_attention_decode_paged_ref,
            pallas=flash_decode_paged_pallas,
            doc="block-table paged decode attention", tuning=())
register_op("attention_prefill_chunk", reference=_attention_prefill_chunk_ref,
            pallas=flash_prefill_chunk_pallas,
            doc="chunked-prefill attention (C-token query block vs cache)",
            tuning="flash_prefill")
register_op("attention_prefill_chunk_paged",
            reference=_attention_prefill_chunk_paged_ref,
            pallas=flash_prefill_chunk_paged_pallas,
            doc="block-table paged chunked-prefill attention", tuning=())
register_op("attention_decode_paged_quant",
            reference=_attention_decode_paged_quant_ref,
            pallas=flash_decode_paged_quant_pallas,
            doc="int8 paged decode attention (in-kernel per-page dequant)",
            tuning="flash_decode_paged_quant")
register_op("attention_prefill_chunk_paged_quant",
            reference=_attention_prefill_chunk_paged_quant_ref,
            pallas=flash_prefill_chunk_paged_quant_pallas,
            doc="int8 paged chunked-prefill attention (in-kernel dequant)",
            tuning="flash_prefill_paged_quant")
register_op("ssd_scan", reference=ref.ssd_scan, pallas=ssd_scan_pallas,
            doc="Mamba-2 SSD chunked scan (fwd ported; bwd oracle vjp)",
            tuning="ssd_scan")
register_op("ssd_prefill_chunk", reference=ref.ssd_scan,
            pallas=ssd_scan_pallas,
            doc="chunked-SSD serving scan (C-token chunk vs carried state; "
                "decode is the C=1 case)",
            tuning="ssd_prefill_chunk")
