"""Blockwise (flash) attention Pallas kernels — GQA, causal, sliding-window.

The LM-zoo's dominant compute hot-spot.  TPU-native design: online-softmax
accumulation in VMEM f32 scratch across the sequential KV-block grid axis;
Q/KV tiles are MXU-aligned; GQA is expressed *in the BlockSpec index maps*
(kv block index = q_head // group) so grouped KV is never materialized
g-fold — the paper's "avoid layout-conversion copies at boundaries" lesson
applied to head layout.

Kernels:
  _flash_fwd   : grid (B, Hq, nQ, nK) -> out, lse (lse carried as a
                 (…, Sq, 1) column so its block obeys Mosaic's tiling rule)
  _flash_dq    : grid (B, Hq, nQ, nK) -> dq
  _flash_dkv   : grid (B, Hkv, nK, g*nQ) -> dk, dv  (inner axis walks the
                 g q-heads of the group × their q blocks; scratch persists)
  _flash_decode: single-q-row attention against a KV cache with *dynamic*
                 valid length (SMEM scalar), for serve_step.
  _flash_decode_paged: the same online softmax against a *paged* cache —
                 the per-row block table is a scalar-prefetch operand, so
                 the physical page each grid step DMAs is chosen in the
                 BlockSpec index map (the paper's "keep layout conversion
                 out of the compute loop" lesson: the gather costs an index
                 lookup, never a materialized copy of the cache).
  _flash_prefill_chunk[_paged]: chunked prompt ingestion — a (C, hd)
                 query block per row attends causally to the cache plus the
                 in-chunk tokens (written before the call), with per-row
                 ``start``/``width`` scalars; the paged variant reuses the
                 decode block-table indirection.

Causal/window block skipping uses pl.when so fully-masked tiles do no MXU
work (they still schedule — negligible next to the saved matmuls).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import pallas_compat as plc

from repro.core.policy import interpret_default
from repro.core.registry import get_tuning
from repro.tuning.shapes import shape_class

NEG_INF = float(-1e30)


def _mask(s, iq, ik, bq, bk, *, causal, window, sk):
    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    m = kpos < sk  # kv padding
    if causal:
        m &= kpos <= qpos
    if window is not None:
        m &= kpos > qpos - window
    return jnp.where(m, s, NEG_INF)


def _flash_fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, scale, causal, window, n_k, bq, bk, sk,
):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # block-level skip for causality / window
    run = jnp.bool_(True)
    if causal:
        run &= ik * bk <= (iq + 1) * bq - 1
    if window is not None:
        run &= (ik + 1) * bk - 1 > iq * bq - window

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        s = _mask(s, iq, ik, bq, bk, causal=causal, window=window, sk=sk)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ik == n_k - 1)
    def _done():
        l = l_ref[...]
        l_safe = jnp.where(l == 0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(l_safe)


def _pad_seq(x, block, axis):
    pad = (-x.shape[axis]) % block
    if pad:
        cfg = [(0, 0)] * x.ndim
        cfg[axis] = (0, pad)
        x = jnp.pad(x, cfg)
    return x


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "interpret"),
)
def flash_attention_pallas(
    q: jax.Array,  # (B, Sq, Hq, D)
    k: jax.Array,  # (B, Sk, Hkv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    interpret=None,
):
    """Returns (out (B,Sq,Hq,D), lse (B,Hq,Sq))."""
    if interpret is None:
        interpret = interpret_default()
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    g = hq // hkv
    scale = scale if scale is not None else float(1.0 / np.sqrt(d))
    t = get_tuning("flash_attention", key=shape_class(d=d, s=sk),
                   bq=128, bk=128)
    bq, bk = min(t["bq"], sq), min(t["bk"], sk)
    qt = _pad_seq(q.transpose(0, 2, 1, 3), bq, 2)    # (B,Hq,Sq',D)
    kt = _pad_seq(k.transpose(0, 2, 1, 3), bk, 2)    # (B,Hkv,Sk',D)
    vt = _pad_seq(v.transpose(0, 2, 1, 3), bk, 2)
    n_q, n_k = qt.shape[2] // bq, kt.shape[2] // bk
    grid = (b, hq, n_q, n_k)
    out, lse = pl.pallas_call(
        functools.partial(
            _flash_fwd_kernel,
            scale=scale, causal=causal, window=window,
            n_k=n_k, bq=bq, bk=bk, sk=sk,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, i, j, g=g: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, i, j, g=g: (b_, h // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h, i, j: (b_, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(qt.shape, q.dtype),
            jax.ShapeDtypeStruct(qt.shape[:3] + (1,), jnp.float32),
        ],
        scratch_shapes=[
            plc.VMEM((bq, d), jnp.float32),
            plc.VMEM((bq, 1), jnp.float32),
            plc.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=plc.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        name="repro_flash_fwd",
    )(qt, kt, vt)
    out = out[:, :, :sq].transpose(0, 2, 1, 3)
    return out, lse[:, :, :sq, 0]


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _flash_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, dq_ref, acc_ref,
    *, scale, causal, window, n_k, bq, bk, sk,
):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = jnp.bool_(True)
    if causal:
        run &= ik * bk <= (iq + 1) * bq - 1
    if window is not None:
        run &= (ik + 1) * bk - 1 > iq * bq - window

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]                           # (bq,1)
        dd = dd_ref[0, 0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        s = _mask(s, iq, ik, bq, bk, causal=causal, window=window, sk=sk)
        p = jnp.exp(s - lse)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - dd)
        acc_ref[...] += jnp.dot(ds, k, preferred_element_type=jnp.float32) * scale

    @pl.when(ik == n_k - 1)
    def _done():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _flash_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, scale, causal, window, n_q, n_inner, bq, bk, sk, sq,
):
    ik, inner = pl.program_id(2), pl.program_id(3)
    iq = inner % n_q

    @pl.when(inner == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    run = jnp.bool_(True)
    if causal:
        run &= ik * bk <= (iq + 1) * bq - 1
    if window is not None:
        run &= (ik + 1) * bk - 1 > iq * bq - window

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        dd = dd_ref[0, 0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        s = _mask(s, iq, ik, bq, bk, causal=causal, window=window, sk=sk)
        # mask padded q rows too (their lse is garbage)
        qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        p = jnp.where(qpos < sq, jnp.exp(s - lse), 0.0)
        dv_acc[...] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - dd)
        dk_acc[...] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32) * scale

    @pl.when(inner == n_inner - 1)
    def _done():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "interpret"),
)
def flash_attention_bwd_pallas(
    q, k, v, out, lse, do,
    *, causal=True, window=None, scale=None, interpret=None,
):
    """Returns (dq, dk, dv)."""
    if interpret is None:
        interpret = interpret_default()
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    g = hq // hkv
    scale = scale if scale is not None else float(1.0 / np.sqrt(d))
    t = get_tuning("flash_attention", key=shape_class(d=d, s=sk),
                   bq=128, bk=128)
    bq, bk = min(t["bq"], sq), min(t["bk"], sk)
    qt = _pad_seq(q.transpose(0, 2, 1, 3), bq, 2)
    kt = _pad_seq(k.transpose(0, 2, 1, 3), bk, 2)
    vt = _pad_seq(v.transpose(0, 2, 1, 3), bk, 2)
    dot = _pad_seq(do.transpose(0, 2, 1, 3), bq, 2)
    ot = _pad_seq(out.transpose(0, 2, 1, 3), bq, 2)
    # per-row scalars travel as (…, Sq', 1) columns: a (bq, 1) block is the
    # layout Mosaic accepts for a row vector ((8, 128)-or-full-dim rule)
    dd = jnp.sum(dot.astype(jnp.float32) * ot.astype(jnp.float32), axis=-1,
                 keepdims=True)
    lse_p = _pad_seq(lse[..., None], bq, 2)
    n_q, n_k = qt.shape[2] // bq, kt.shape[2] // bk
    # --- dq ---
    grid = (b, hq, n_q, n_k)
    dq = pl.pallas_call(
        functools.partial(
            _flash_dq_kernel,
            scale=scale, causal=causal, window=window,
            n_k=n_k, bq=bq, bk=bk, sk=sk,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, i, j, g=g: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, i, j, g=g: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h, i, j: (b_, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[plc.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        compiler_params=plc.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        name="repro_flash_dq",
    )(qt, kt, vt, dot, lse_p, dd)
    # --- dk, dv --- inner axis = (q-head-in-group, q-block)
    n_inner = g * n_q
    grid2 = (b, hkv, n_k, n_inner)

    def qix(b_, h, jk, inner, g=g, n_q=n_q):
        return (b_, h * g + inner // n_q, inner % n_q, 0)

    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_dkv_kernel,
            scale=scale, causal=causal, window=window,
            n_q=n_q, n_inner=n_inner, bq=bq, bk=bk, sk=sk, sq=sq,
        ),
        grid=grid2,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), qix),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, jk, inner: (b_, h, jk, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, jk, inner: (b_, h, jk, 0)),
            pl.BlockSpec((1, 1, bq, d), qix),
            pl.BlockSpec((1, 1, bq, 1), qix),
            pl.BlockSpec((1, 1, bq, 1), qix),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, jk, inner: (b_, h, jk, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, jk, inner: (b_, h, jk, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(kt.shape, k.dtype),
            jax.ShapeDtypeStruct(vt.shape, v.dtype),
        ],
        scratch_shapes=[
            plc.VMEM((bk, d), jnp.float32),
            plc.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=plc.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        name="repro_flash_dkv",
    )(qt, kt, vt, dot, lse_p, dd)
    dq = dq[:, :, :sq].transpose(0, 2, 1, 3)
    dk = dk[:, :, :sk].transpose(0, 2, 1, 3)
    dv = dv[:, :, :sk].transpose(0, 2, 1, 3)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Decode: one new token vs a KV cache of dynamic valid length (SMEM scalar)
# ---------------------------------------------------------------------------

# The contiguous and paged decode kernels share one online-softmax block
# (init / accumulate-a-KV-tile / finalize) so a numerics change cannot
# de-synchronize the two layouts; they differ only in how a grid step maps
# to cache positions (kpos_base) and in which tiles are skipped (`run`).

def _decode_init(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _online_update(s, v, acc_ref, m_ref, l_ref):
    """One online-softmax accumulation of a masked score tile ``s``."""
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new


def _decode_accum(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, *,
                  kpos_base, cache_len, window, scale,
                  k_s=None, v_s=None, bs=None):
    """``k_s``/``v_s`` (quantized pools) are the page's per-head dequant
    scales — applied right after the f32 upcast, so scores and the online
    softmax always accumulate in f32 regardless of storage dtype.  ``bs``
    statically unrolls the tile into bs-row sub-tiles (the quantized
    kernels' tuning knob); ``None`` keeps the single-tile accumulation
    bit-identical to the pre-quantization kernels."""
    q = q_ref[0, 0].astype(jnp.float32)           # (g, d) rows = heads grp
    k = k_ref[0, 0].astype(jnp.float32)           # (tile, d)
    v = v_ref[0, 0].astype(jnp.float32)
    if k_s is not None:
        k = k * k_s
        v = v * v_s
    tile = k.shape[0]
    step = tile if bs is None else bs
    for t in range(tile // step):
        k_t = jax.lax.slice_in_dim(k, t * step, (t + 1) * step, axis=0)
        v_t = jax.lax.slice_in_dim(v, t * step, (t + 1) * step, axis=0)
        s = jnp.dot(q, k_t.T, preferred_element_type=jnp.float32) * scale
        kpos = (kpos_base + t * step
                + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
        valid = kpos < cache_len
        if window is not None:
            valid &= kpos >= cache_len - window
        s = jnp.where(valid, s, NEG_INF)
        _online_update(s, v_t, acc_ref, m_ref, l_ref)


def _decode_finalize(o_ref, acc_ref, l_ref):
    l = l_ref[...]
    o_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0, 1.0, l)).astype(
        o_ref.dtype
    )


def _flash_decode_kernel(
    len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale, n_k, bk, window,
):
    ik = pl.program_id(2)
    cache_len = len_ref[pl.program_id(0)]  # per-sequence valid length

    @pl.when(ik == 0)
    def _init():
        _decode_init(acc_ref, m_ref, l_ref)

    # skip blocks entirely beyond the valid length (or before the window)
    run = ik * bk < cache_len
    if window is not None:
        run &= (ik + 1) * bk - 1 >= cache_len - window

    @pl.when(run)
    def _body():
        _decode_accum(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                      kpos_base=ik * bk, cache_len=cache_len,
                      window=window, scale=scale)

    @pl.when(ik == n_k - 1)
    def _done():
        _decode_finalize(o_ref, acc_ref, l_ref)


@functools.partial(
    jax.jit, static_argnames=("window", "scale", "interpret")
)
def flash_decode_pallas(
    q: jax.Array,        # (B, Hq, D)  one token per sequence
    k_cache: jax.Array,  # (B, Smax, Hkv, D)
    v_cache: jax.Array,
    cache_len: jax.Array,  # int32 () or (B,): valid prefix len (incl. new tok)
    *,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    interpret=None,
):
    if interpret is None:
        interpret = interpret_default()
    b, hq, d = q.shape
    _, smax, hkv, _ = k_cache.shape
    g = hq // hkv
    scale = scale if scale is not None else float(1.0 / np.sqrt(d))
    t = get_tuning("flash_decode", key=shape_class(s=smax), bk=512)
    bk = min(t["bk"], smax)
    kt = _pad_seq(k_cache.transpose(0, 2, 1, 3), bk, 2)  # (B,Hkv,S',D)
    vt = _pad_seq(v_cache.transpose(0, 2, 1, 3), bk, 2)
    n_k = kt.shape[2] // bk
    # group query heads of one kv head into rows of a single matmul
    qg = q.reshape(b, hkv, g, d)
    grid = (b, hkv, n_k)
    out = pl.pallas_call(
        functools.partial(
            _flash_decode_kernel, scale=scale, n_k=n_k, bk=bk, window=window
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=plc.MemorySpace.SMEM),
            pl.BlockSpec((1, 1, g, d), lambda b_, h, j: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, j: (b_, h, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, j: (b_, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda b_, h, j: (b_, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        scratch_shapes=[
            plc.VMEM((g, d), jnp.float32),
            plc.VMEM((g, 1), jnp.float32),
            plc.VMEM((g, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=plc.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        name="repro_flash_decode",
    )(
        jnp.broadcast_to(cache_len.reshape(-1).astype(jnp.int32), (b,)),
        qg, kt, vt,
    )
    return out.reshape(b, hq, d)


# ---------------------------------------------------------------------------
# Paged decode: block-table-indirect KV pages, gathered in the index map
# ---------------------------------------------------------------------------

def _head_major(k_pages, v_pages):
    """The page pools in the paged kernels' layout, ``(n_pages, Hkv, page,
    D)``: a relayout of the whole pool on every call, staged under the
    ``kv_pool`` name scope so a device trace can tell it from attention."""
    with jax.named_scope("kv_pool"):
        return k_pages.transpose(0, 2, 1, 3), v_pages.transpose(0, 2, 1, 3)


def _flash_decode_paged_kernel(
    len_ref, bt_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale, n_b, page, window,
):
    ib, j = pl.program_id(0), pl.program_id(2)
    cache_len = len_ref[ib]

    @pl.when(j == 0)
    def _init():
        _decode_init(acc_ref, m_ref, l_ref)

    # skip unmapped pages and pages entirely beyond the valid prefix
    run = (bt_ref[ib, j] >= 0) & (j * page < cache_len)
    if window is not None:
        run &= (j + 1) * page - 1 >= cache_len - window

    @pl.when(run)
    def _body():
        _decode_accum(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                      kpos_base=j * page, cache_len=cache_len,
                      window=window, scale=scale)

    @pl.when(j == n_b - 1)
    def _done():
        _decode_finalize(o_ref, acc_ref, l_ref)


@functools.partial(
    jax.jit, static_argnames=("window", "scale", "interpret")
)
def flash_decode_paged_pallas(
    q: jax.Array,            # (B, Hq, D)  one token per sequence
    k_pages: jax.Array,      # (n_pages, page_size, Hkv, D) shared page pool
    v_pages: jax.Array,
    cache_len: jax.Array,    # int32 () or (B,): valid prefix incl. new token
    block_table: jax.Array,  # (B, max_blocks) int32; -1 = unmapped
    *,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    interpret=None,
):
    """Decode attention over the paged KV layout (contract: pager.py).

    Grid (B, Hkv, max_blocks); the KV BlockSpec index maps read the
    scalar-prefetched block table to select the physical page — unmapped
    blocks clamp to page 0 and are skipped by ``pl.when``, so their DMA is
    harmless and no MXU work runs.
    """
    if interpret is None:
        interpret = interpret_default()
    b, hq, d = q.shape
    n_pages, page, hkv, _ = k_pages.shape
    n_b = block_table.shape[1]
    g = hq // hkv
    scale = scale if scale is not None else float(1.0 / np.sqrt(d))
    kt, vt = _head_major(k_pages, v_pages)
    qg = q.reshape(b, hkv, g, d)
    lens = jnp.broadcast_to(
        jnp.asarray(cache_len, jnp.int32).reshape(-1), (b,)
    )

    def kv_ix(b_, h, j, lens_ref, bt_ref):
        return (jnp.maximum(bt_ref[b_, j], 0), h, 0, 0)

    grid_spec = plc.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                    # lens, block table
        grid=(b, hkv, n_b),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b_, h, j, *_: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, page, d), kv_ix),
            pl.BlockSpec((1, 1, page, d), kv_ix),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda b_, h, j, *_: (b_, h, 0, 0)),
        scratch_shapes=[
            plc.VMEM((g, d), jnp.float32),
            plc.VMEM((g, 1), jnp.float32),
            plc.VMEM((g, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _flash_decode_paged_kernel,
            scale=scale, n_b=n_b, page=page, window=window,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret,
        compiler_params=plc.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        name="repro_flash_decode_paged",
    )(lens, block_table, qg, kt, vt)
    return out.reshape(b, hq, d)


# ---------------------------------------------------------------------------
# Quantized paged decode: int8 pages, per-(page, head) scales prefetched to
# SMEM and applied inside the kernel right after the upcast
# ---------------------------------------------------------------------------

def _flash_decode_paged_quant_kernel(
    len_ref, bt_ref, ksc_ref, vsc_ref, q_ref, k_ref, v_ref, o_ref,
    acc_ref, m_ref, l_ref,
    *, scale, n_b, page, bs, window,
):
    ib, h, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    cache_len = len_ref[ib]
    # the page the block table routed this grid step to — same clamp as
    # the BlockSpec index map, so skipped steps read scale 0 harmlessly
    pg = jnp.maximum(bt_ref[ib, j], 0)
    k_s = ksc_ref[pg, h]
    v_s = vsc_ref[pg, h]

    @pl.when(j == 0)
    def _init():
        _decode_init(acc_ref, m_ref, l_ref)

    run = (bt_ref[ib, j] >= 0) & (j * page < cache_len)
    if window is not None:
        run &= (j + 1) * page - 1 >= cache_len - window

    @pl.when(run)
    def _body():
        _decode_accum(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                      kpos_base=j * page, cache_len=cache_len,
                      window=window, scale=scale,
                      k_s=k_s, v_s=v_s, bs=bs)

    @pl.when(j == n_b - 1)
    def _done():
        _decode_finalize(o_ref, acc_ref, l_ref)


@functools.partial(
    jax.jit, static_argnames=("window", "scale", "interpret")
)
def flash_decode_paged_quant_pallas(
    q: jax.Array,            # (B, Hq, D)  one token per sequence
    k_pages: jax.Array,      # (n_pages, page_size, Hkv, D) int8 page pool
    v_pages: jax.Array,
    k_scale: jax.Array,      # (n_pages, Hkv) f32 per-(page, head) scales
    v_scale: jax.Array,
    cache_len: jax.Array,    # int32 () or (B,): valid prefix incl. new token
    block_table: jax.Array,  # (B, max_blocks) int32; -1 = unmapped
    *,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    interpret=None,
):
    """Decode attention over the quantized paged KV layout.

    Same block-table indirection as ``flash_decode_paged_pallas``; the
    per-(page, head) scale pools ride the scalar prefetch into SMEM and
    the kernel multiplies them in right after the int8 -> f32 upcast, so
    scores and the online softmax accumulate in f32 (R007).  ``bs``
    (tuned) statically sub-tiles the page axis of the accumulation.
    """
    if interpret is None:
        interpret = interpret_default()
    b, hq, d = q.shape
    n_pages, page, hkv, _ = k_pages.shape
    n_b = block_table.shape[1]
    g = hq // hkv
    scale = scale if scale is not None else float(1.0 / np.sqrt(d))
    t = get_tuning("flash_decode_paged_quant", key=shape_class(p=page),
                   bs=16)
    bs = max(1, min(int(t["bs"]), page))
    while page % bs:
        bs //= 2
    kt, vt = _head_major(k_pages, v_pages)
    qg = q.reshape(b, hkv, g, d)
    lens = jnp.broadcast_to(
        jnp.asarray(cache_len, jnp.int32).reshape(-1), (b,)
    )

    def kv_ix(b_, h, j, lens_ref, bt_ref, ksc_ref, vsc_ref):
        return (jnp.maximum(bt_ref[b_, j], 0), h, 0, 0)

    grid_spec = plc.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,        # lens, block table, k/v scale pools
        grid=(b, hkv, n_b),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b_, h, j, *_: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, page, d), kv_ix),
            pl.BlockSpec((1, 1, page, d), kv_ix),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda b_, h, j, *_: (b_, h, 0, 0)),
        scratch_shapes=[
            plc.VMEM((g, d), jnp.float32),
            plc.VMEM((g, 1), jnp.float32),
            plc.VMEM((g, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _flash_decode_paged_quant_kernel,
            scale=scale, n_b=n_b, page=page, bs=bs, window=window,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret,
        compiler_params=plc.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        name="repro_flash_decode_paged_quant",
    )(lens, block_table, k_scale.astype(jnp.float32),
      v_scale.astype(jnp.float32), qg, kt, vt)
    return out.reshape(b, hq, d)


# ---------------------------------------------------------------------------
# Chunked prefill: a (C, hd) query block per row vs the already-written cache
# ---------------------------------------------------------------------------

# Multi-token prompt ingestion.  The chunk's K/V are written into the cache
# *before* attention runs (same order as decode, which writes the current
# token first), so a grid step only needs the causal mask to separate
# in-chunk from already-cached keys.  The query block folds the GQA group
# and the chunk into one matmul: row r = gq * C + i is (head-in-group gq,
# chunk offset i) at absolute position start + i.  Padding rows (i >=
# width) alias the last real position so every softmax row keeps at least
# one finite score — their outputs are garbage-but-finite and the caller
# discards them (NaNs here would leak into real tokens through MoE
# dispatch buffers).

def _prefill_chunk_mask(s, *, kpos_base, start, width, c, window):
    i = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % c
    qpos = start + jnp.minimum(i, width - 1)
    kpos = kpos_base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = kpos <= qpos
    if window is not None:
        valid &= kpos > qpos - window
    return jnp.where(valid, s, NEG_INF)


def _prefill_chunk_accum(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, *,
                         kpos_base, start, width, c, window, scale,
                         k_s=None, v_s=None, bs=None):
    # ``k_s``/``v_s``/``bs`` as in ``_decode_accum``: per-page dequant
    # scales applied after the f32 upcast, optional static sub-tiling
    q = q_ref[0, 0].astype(jnp.float32)           # (g*c, d)
    k = k_ref[0, 0].astype(jnp.float32)           # (tile, d)
    v = v_ref[0, 0].astype(jnp.float32)
    if k_s is not None:
        k = k * k_s
        v = v * v_s
    tile = k.shape[0]
    step = tile if bs is None else bs
    for t in range(tile // step):
        k_t = jax.lax.slice_in_dim(k, t * step, (t + 1) * step, axis=0)
        v_t = jax.lax.slice_in_dim(v, t * step, (t + 1) * step, axis=0)
        s = jnp.dot(q, k_t.T, preferred_element_type=jnp.float32) * scale
        s = _prefill_chunk_mask(s, kpos_base=kpos_base + t * step,
                                start=start, width=width, c=c,
                                window=window)
        _online_update(s, v_t, acc_ref, m_ref, l_ref)


def _flash_prefill_chunk_kernel(
    start_ref, w_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale, n_k, bk, c, window,
):
    ib, ik = pl.program_id(0), pl.program_id(2)
    start = start_ref[ib]
    width = w_ref[ib]

    @pl.when(ik == 0)
    def _init():
        _decode_init(acc_ref, m_ref, l_ref)

    # the last key any chunk query may see is start + width - 1; the first
    # query sits at start, so a window cuts tiles before start - window
    run = ik * bk <= start + width - 1
    if window is not None:
        run &= (ik + 1) * bk - 1 > start - window

    @pl.when(run)
    def _body():
        _prefill_chunk_accum(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                             kpos_base=ik * bk, start=start, width=width,
                             c=c, window=window, scale=scale)

    @pl.when(ik == n_k - 1)
    def _done():
        _decode_finalize(o_ref, acc_ref, l_ref)


@functools.partial(
    jax.jit, static_argnames=("window", "scale", "interpret")
)
def flash_prefill_chunk_pallas(
    q: jax.Array,        # (B, C, Hq, D)  C prompt tokens per sequence
    k_cache: jax.Array,  # (B, Smax, Hkv, D) — chunk K/V already written
    v_cache: jax.Array,
    start: jax.Array,    # int32 () or (B,): absolute position of chunk tok 0
    width: jax.Array,    # int32 () or (B,): real tokens in the chunk (1..C)
    *,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    interpret=None,
):
    """Chunked-prefill attention over the contiguous cache layout.

    Query i of row b attends causally to absolute positions
    ``<= start[b] + i`` (window-limited when set); rows ``i >= width[b]``
    are padding and return finite garbage.  Kept in lock-step with the
    jnp oracle in ``repro.kernels.ops``.
    """
    if interpret is None:
        interpret = interpret_default()
    b, c, hq, d = q.shape
    _, smax, hkv, _ = k_cache.shape
    g = hq // hkv
    scale = scale if scale is not None else float(1.0 / np.sqrt(d))
    t = get_tuning("flash_prefill", key=shape_class(c=c, s=smax),
                   bk=512)
    bk = min(t["bk"], smax)
    kt = _pad_seq(k_cache.transpose(0, 2, 1, 3), bk, 2)   # (B,Hkv,S',D)
    vt = _pad_seq(v_cache.transpose(0, 2, 1, 3), bk, 2)
    n_k = kt.shape[2] // bk
    # fold (group head, chunk offset) into the matmul's row axis
    qg = q.reshape(b, c, hkv, g, d).transpose(0, 2, 3, 1, 4)
    qg = qg.reshape(b, hkv, g * c, d)
    grid = (b, hkv, n_k)
    out = pl.pallas_call(
        functools.partial(
            _flash_prefill_chunk_kernel,
            scale=scale, n_k=n_k, bk=bk, c=c, window=window,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=plc.MemorySpace.SMEM),
            pl.BlockSpec(memory_space=plc.MemorySpace.SMEM),
            pl.BlockSpec((1, 1, g * c, d), lambda b_, h, j: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, j: (b_, h, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, j: (b_, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g * c, d), lambda b_, h, j: (b_, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g * c, d), q.dtype),
        scratch_shapes=[
            plc.VMEM((g * c, d), jnp.float32),
            plc.VMEM((g * c, 1), jnp.float32),
            plc.VMEM((g * c, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=plc.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        name="repro_flash_prefill_chunk",
    )(
        jnp.broadcast_to(jnp.asarray(start, jnp.int32).reshape(-1), (b,)),
        jnp.broadcast_to(jnp.asarray(width, jnp.int32).reshape(-1), (b,)),
        qg, kt, vt,
    )
    out = out.reshape(b, hkv, g, c, d).transpose(0, 3, 1, 2, 4)
    return out.reshape(b, c, hq, d)


def _flash_prefill_chunk_paged_kernel(
    start_ref, w_ref, bt_ref, q_ref, k_ref, v_ref, o_ref,
    acc_ref, m_ref, l_ref,
    *, scale, n_b, page, c, window,
):
    ib, j = pl.program_id(0), pl.program_id(2)
    start = start_ref[ib]
    width = w_ref[ib]

    @pl.when(j == 0)
    def _init():
        _decode_init(acc_ref, m_ref, l_ref)

    # skip unmapped pages and pages entirely beyond the chunk's last key
    run = (bt_ref[ib, j] >= 0) & (j * page <= start + width - 1)
    if window is not None:
        run &= (j + 1) * page - 1 > start - window

    @pl.when(run)
    def _body():
        _prefill_chunk_accum(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                             kpos_base=j * page, start=start, width=width,
                             c=c, window=window, scale=scale)

    @pl.when(j == n_b - 1)
    def _done():
        _decode_finalize(o_ref, acc_ref, l_ref)


@functools.partial(
    jax.jit, static_argnames=("window", "scale", "interpret")
)
def flash_prefill_chunk_paged_pallas(
    q: jax.Array,            # (B, C, Hq, D)  C prompt tokens per sequence
    k_pages: jax.Array,      # (n_pages, page_size, Hkv, D) shared page pool
    v_pages: jax.Array,
    start: jax.Array,        # int32 () or (B,): absolute pos of chunk tok 0
    width: jax.Array,        # int32 () or (B,): real tokens in the chunk
    block_table: jax.Array,  # (B, max_blocks) int32; -1 = unmapped
    *,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    interpret=None,
):
    """Chunked-prefill attention over the paged layout (contract: pager.py).

    Same scalar-prefetch indirection as ``flash_decode_paged_pallas`` — the
    block table picks the physical page in the BlockSpec index map — with
    the multi-row causal chunk mask of ``flash_prefill_chunk_pallas``.
    Every block covering ``start .. start+width-1`` must be mapped before
    the call (``pager.alloc_range``).
    """
    if interpret is None:
        interpret = interpret_default()
    b, c, hq, d = q.shape
    n_pages, page, hkv, _ = k_pages.shape
    n_b = block_table.shape[1]
    g = hq // hkv
    scale = scale if scale is not None else float(1.0 / np.sqrt(d))
    kt, vt = _head_major(k_pages, v_pages)
    qg = q.reshape(b, c, hkv, g, d).transpose(0, 2, 3, 1, 4)
    qg = qg.reshape(b, hkv, g * c, d)
    starts = jnp.broadcast_to(
        jnp.asarray(start, jnp.int32).reshape(-1), (b,)
    )
    widths = jnp.broadcast_to(
        jnp.asarray(width, jnp.int32).reshape(-1), (b,)
    )

    def kv_ix(b_, h, j, starts_ref, w_ref, bt_ref):
        return (jnp.maximum(bt_ref[b_, j], 0), h, 0, 0)

    grid_spec = plc.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                    # starts, widths, table
        grid=(b, hkv, n_b),
        in_specs=[
            pl.BlockSpec((1, 1, g * c, d), lambda b_, h, j, *_: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, page, d), kv_ix),
            pl.BlockSpec((1, 1, page, d), kv_ix),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, g * c, d), lambda b_, h, j, *_: (b_, h, 0, 0)
        ),
        scratch_shapes=[
            plc.VMEM((g * c, d), jnp.float32),
            plc.VMEM((g * c, 1), jnp.float32),
            plc.VMEM((g * c, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _flash_prefill_chunk_paged_kernel,
            scale=scale, n_b=n_b, page=page, c=c, window=window,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g * c, d), q.dtype),
        interpret=interpret,
        compiler_params=plc.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        name="repro_flash_prefill_chunk_paged",
    )(starts, widths, block_table, qg, kt, vt)
    out = out.reshape(b, hkv, g, c, d).transpose(0, 3, 1, 2, 4)
    return out.reshape(b, c, hq, d)


def _flash_prefill_chunk_paged_quant_kernel(
    start_ref, w_ref, bt_ref, ksc_ref, vsc_ref, q_ref, k_ref, v_ref, o_ref,
    acc_ref, m_ref, l_ref,
    *, scale, n_b, page, bs, c, window,
):
    ib, h, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    start = start_ref[ib]
    width = w_ref[ib]
    pg = jnp.maximum(bt_ref[ib, j], 0)
    k_s = ksc_ref[pg, h]
    v_s = vsc_ref[pg, h]

    @pl.when(j == 0)
    def _init():
        _decode_init(acc_ref, m_ref, l_ref)

    run = (bt_ref[ib, j] >= 0) & (j * page <= start + width - 1)
    if window is not None:
        run &= (j + 1) * page - 1 > start - window

    @pl.when(run)
    def _body():
        _prefill_chunk_accum(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                             kpos_base=j * page, start=start, width=width,
                             c=c, window=window, scale=scale,
                             k_s=k_s, v_s=v_s, bs=bs)

    @pl.when(j == n_b - 1)
    def _done():
        _decode_finalize(o_ref, acc_ref, l_ref)


@functools.partial(
    jax.jit, static_argnames=("window", "scale", "interpret")
)
def flash_prefill_chunk_paged_quant_pallas(
    q: jax.Array,            # (B, C, Hq, D)  C prompt tokens per sequence
    k_pages: jax.Array,      # (n_pages, page_size, Hkv, D) int8 page pool
    v_pages: jax.Array,
    k_scale: jax.Array,      # (n_pages, Hkv) f32 per-(page, head) scales
    v_scale: jax.Array,
    start: jax.Array,        # int32 () or (B,): absolute pos of chunk tok 0
    width: jax.Array,        # int32 () or (B,): real tokens in the chunk
    block_table: jax.Array,  # (B, max_blocks) int32; -1 = unmapped
    *,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    interpret=None,
):
    """Chunked-prefill attention over the quantized paged layout.

    ``flash_prefill_chunk_paged_pallas`` with the scale pools added to
    the scalar prefetch: dequant happens inside the kernel after the
    int8 -> f32 upcast, accumulation stays f32 (R007).  The chunk's own
    K/V must already be written (``pager.write_page_chunk_quant``).
    """
    if interpret is None:
        interpret = interpret_default()
    b, c, hq, d = q.shape
    n_pages, page, hkv, _ = k_pages.shape
    n_b = block_table.shape[1]
    g = hq // hkv
    scale = scale if scale is not None else float(1.0 / np.sqrt(d))
    t = get_tuning("flash_prefill_paged_quant",
                   key=shape_class(c=c, p=page), bs=16)
    bs = max(1, min(int(t["bs"]), page))
    while page % bs:
        bs //= 2
    kt, vt = _head_major(k_pages, v_pages)
    qg = q.reshape(b, c, hkv, g, d).transpose(0, 2, 3, 1, 4)
    qg = qg.reshape(b, hkv, g * c, d)
    starts = jnp.broadcast_to(
        jnp.asarray(start, jnp.int32).reshape(-1), (b,)
    )
    widths = jnp.broadcast_to(
        jnp.asarray(width, jnp.int32).reshape(-1), (b,)
    )

    def kv_ix(b_, h, j, starts_ref, w_ref, bt_ref, ksc_ref, vsc_ref):
        return (jnp.maximum(bt_ref[b_, j], 0), h, 0, 0)

    grid_spec = plc.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,    # starts, widths, table, k/v scale pools
        grid=(b, hkv, n_b),
        in_specs=[
            pl.BlockSpec((1, 1, g * c, d), lambda b_, h, j, *_: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, page, d), kv_ix),
            pl.BlockSpec((1, 1, page, d), kv_ix),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, g * c, d), lambda b_, h, j, *_: (b_, h, 0, 0)
        ),
        scratch_shapes=[
            plc.VMEM((g * c, d), jnp.float32),
            plc.VMEM((g * c, 1), jnp.float32),
            plc.VMEM((g * c, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _flash_prefill_chunk_paged_quant_kernel,
            scale=scale, n_b=n_b, page=page, bs=bs, c=c, window=window,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g * c, d), q.dtype),
        interpret=interpret,
        compiler_params=plc.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        name="repro_flash_prefill_chunk_paged_quant",
    )(starts, widths, block_table, k_scale.astype(jnp.float32),
      v_scale.astype(jnp.float32), qg, kt, vt)
    out = out.reshape(b, hkv, g, c, d).transpose(0, 3, 1, 2, 4)
    return out.reshape(b, c, hq, d)
