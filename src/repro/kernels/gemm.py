"""Tiled GEMM Pallas kernel — the workhorse behind Caffe's im2col+GEMM conv
and the InnerProduct layer, and the LM stack's projections.

TPU adaptation of the paper's GEMM usage: instead of delegating to OpenBLAS
(CPU) / cuBLAS (GPU), the portable op carries its own MXU-tiled kernel.
Grid = (cdiv(M, bm), cdiv(N, bn), K/bk); the K axis is the innermost,
sequential ("arbitrary") dimension so a VMEM f32 scratch accumulator
persists across K steps; output is written once on the last K step.

Blocks come from :func:`plan_blocks`, computed from the call's own shape
and dtype, unless the tuning registry holds an entry for the call's shape
class — PHAST's "tuning parameters without source change".  The plan pads
or copies no operand: bk divides K (save a K that is no multiple of 128
and too long to take whole), and the M and N edges are ``pl.cdiv`` blocks
whose out-of-range rows and columns Pallas drops on write (what the edge
blocks read there reaches only those dropped rows and columns).  Each
traced call records its plan in the registry
(``registry.resolved_plans("gemm")``).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import pallas_compat as plc

from repro.core.policy import interpret_default
from repro.core.registry import get_tuning, record_plan
from repro.tuning.shapes import shape_class

# The planner's model of one TPU v5e core: what a grid step costs beyond
# its work (repro_gemm at 128^3 blocks on the chip: 31.785 s of device
# time over 124.6 M grid steps), the bf16 MXU peak and HBM bandwidth.
STEP_S = 0.255e-6
PEAK_FLOPS = 197e12
HBM_BYTES_PER_S = 819e9
# A plan fits the scoped VMEM that Mosaic gives a kernel by default on a
# v5e core.  Asking for more (``vmem_limit_bytes``) takes VMEM from XLA,
# which keeps buffers of the decode step there: the paged attention's
# head-major K/V pools and the layer's sliced weights.
VMEM_BUDGET = 16 << 20
# Largest block tried along M and N; any K-block that divides K is tried.
_MAX_ROWS = 2048
_MAX_LANES = 8192


class GemmPlan(NamedTuple):
    bm: int
    bn: int
    bk: int
    steps: int          # grid steps of one call


def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int, out_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def pad_to(x: jax.Array, mults: tuple) -> jax.Array:
    """Zero-pad trailing edges so every dim is a multiple of ``mults``."""
    pads = [(0, (-d) % m) for d, m in zip(x.shape, mults)]
    if any(p[1] for p in pads):
        return jnp.pad(x, pads)
    return x


def vmem_bytes(bm: int, bn: int, bk: int, item: int, out_item: int) -> int:
    """VMEM of one plan: double-buffered A and B blocks and output block,
    the f32 accumulator and the f32 product of each step's dot.  An output
    block whose lanes are no multiple of 256 takes one more f32 block in
    Mosaic: (1024, 640, 1280) at (2048, 5120, 2560) asks 16.02 MiB of a
    v5e core, which the terms above put at 15.63."""
    f32_blocks = 2 if bn % 256 == 0 else 3
    return 2 * (bm * bk + bk * bn) * item + 4 * f32_blocks * bm * bn \
        + 2 * bm * bn * out_item


def _blocks(d: int, align: int, cap: int) -> set:
    """Legal blocks along a dim of size ``d`` that ``pl.cdiv`` tiles: the
    whole dim, and multiples of ``align`` below it (``align`` times powers
    of two, and divisors of ``d``, which leave no edge block)."""
    out = {d} if d <= cap else set()
    b = align
    while b < min(d, cap + 1):
        out.add(b)
        b *= 2
    out.update(b for b in range(align, min(d, cap + 1), align) if d % b == 0)
    return out


def _k_blocks(k: int) -> set:
    """Legal blocks along K: the whole of K, and multiples of 128 that
    divide it (K is never tiled with an edge block: what an edge block
    read past K would reach every output)."""
    return {k} | {b for b in range(128, k, 128) if k % b == 0}


def _least_time(m: int, k: int, n: int, item: int,
                out_item: int) -> GemmPlan | None:
    best = None
    for bk in _k_blocks(k):
        for bm in _blocks(m, 32 // item, _MAX_ROWS):
            for bn in _blocks(n, 128, _MAX_LANES):
                if vmem_bytes(bm, bn, bk, item, out_item) > VMEM_BUDGET:
                    continue
                gm, gn = pl.cdiv(m, bm), pl.cdiv(n, bn)
                steps = gm * gn * (k // bk)
                mp, np_ = gm * bm, gn * bn
                flops = 2.0 * mp * np_ * k
                hbm = (mp * k * gn + k * np_ * gm) * item + mp * np_ * out_item
                exposed = (bm * bk + bk * bn) * item + bm * bn * out_item
                t = (steps * STEP_S + max(flops / PEAK_FLOPS,
                                          hbm / HBM_BYTES_PER_S)
                     + exposed / HBM_BYTES_PER_S)
                if best is None or (t, steps) < best[:2]:
                    best = (t, steps, GemmPlan(bm, bn, bk, steps))
    return None if best is None else best[2]


@functools.lru_cache(maxsize=None)
def plan_blocks(m: int, k: int, n: int, item: int, out_item: int) -> GemmPlan:
    """Blocks for an (m, k) @ (k, n) call with ``item``-byte operands and an
    ``out_item``-byte output.

    Of the legal plans within ``VMEM_BUDGET``, the one with the least
    modelled time: grid steps x ``STEP_S``, plus the larger of MXU time and
    HBM time of the padded work (A is read once per N-block, B once per
    M-block), plus the first blocks' fetch and the last output block's
    write, which no step overlaps.  Ties go to fewer steps.  Only a K that
    is no multiple of 128 and too long to take whole is padded, to the
    next multiple of 128: its bk then does not divide K.
    """
    plan = _least_time(m, k, n, item, out_item)
    if plan is None and k % 128:
        plan = _least_time(m, k + (-k) % 128, n, item, out_item)
    if plan is None:
        raise ValueError(f"no GEMM blocks for ({m}, {k}) @ ({k}, {n}) fit "
                         f"{VMEM_BUDGET} bytes of VMEM")
    return plan


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def gemm_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    out_dtype=None,
    interpret: bool | None = None,
) -> jax.Array:
    """(M,K) @ (K,N) -> (M,N) via the tiled Pallas kernel."""
    if interpret is None:
        interpret = interpret_default()
    out_dtype = jnp.dtype(out_dtype or a.dtype)
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    plan = plan_blocks(m, k, n, max(a.dtype.itemsize, b.dtype.itemsize),
                       out_dtype.itemsize)
    t = get_tuning("gemm", key=shape_class(m=m, n=n, k=k),
                   bm=plan.bm, bn=plan.bn, bk=plan.bk)
    bm, bn, bk = (min(t["bm"], m), min(t["bn"], n), min(t["bk"], k))
    # only a tuned bk that does not divide K pads, both operands
    a, b = pad_to(a, (1, bk)), pad_to(b, (bk, 1))
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), a.shape[1] // bk)
    record_plan("gemm", (m, k, n),
                GemmPlan(bm, bn, bk, grid[0] * grid[1] * grid[2]))
    return pl.pallas_call(
        functools.partial(_gemm_kernel, n_k=grid[2], out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[plc.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=plc.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        name="repro_gemm",
    )(a, b)
