"""Mamba-2 SSD (state-space duality) chunked-scan Pallas kernel.

TPU-native layout of the SSD algorithm (arXiv:2405.21060): grid
(B, H, n_chunks) with the chunk axis sequential; the running (P, N) state
lives in VMEM f32 scratch across chunk steps.  Every compute inside the
kernel is a 2-D MXU matmul:

    cb       = C @ B^T                      (L,L)   intra-chunk kernel
    y_intra  = (cb ⊙ seg ⊙ dt_u) @ x        (L,P)
    y_inter  = (C ⊙ e^cum) @ state^T        (L,P)
    state'   = e^{cum_L} state + x^T @ (B ⊙ decay·dt)   (P,N)

Operands are laid out head-major so every block obeys Mosaic's tiling rule
(last two block dims divisible by (8, 128) or equal to the array's) for
any head count, down to the C=1 decode chunk: x/y are (B, H, S, P) with a
(chunk, P) block, and the per-token scalars dt and the within-chunk
log-decay cumsum ``cum`` (computed outside the kernel, where the per-head
``A`` is folded in) travel both as (chunk, 1) columns and as (1, chunk)
rows, the two orientations the (L, L) segment matrix needs.

Supports n_groups == 1 (the Mamba-2 2.7B / Zamba2 configuration) and raises
on grouped B/C.  Backward is the reference vjp (recorded, like the
paper's partially-ported blocks).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import pallas_compat as plc

from repro.core.policy import interpret_default
from repro.core.registry import get_tuning
from repro.tuning.shapes import shape_class


def _ssd_kernel(
    x_ref, dtc_ref, dtr_ref, cumc_ref, cumr_ref, b_ref, c_ref, h0_ref,
    y_ref, hf_ref, state_ref, *, n_c: int, chunk: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = h0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)              # (L, P)
    dt_c = dtc_ref[0, 0]                              # (L, 1)
    dt_r = dtr_ref[0, 0, 0]                           # (1, L)
    cum_c = cumc_ref[0, 0]                            # (L, 1)
    cum_r = cumr_ref[0, 0, 0]                         # (1, L)
    bmat = b_ref[0].astype(jnp.float32)               # (L, N)
    cmat = c_ref[0].astype(jnp.float32)               # (L, N)

    seg = cum_c - cum_r                               # (L, L)
    tri = (
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    )
    seg = jnp.where(tri, jnp.exp(seg), 0.0)
    cb = jnp.dot(cmat, bmat.T, preferred_element_type=jnp.float32)
    att = cb * seg * dt_r
    y = jnp.dot(att, x, preferred_element_type=jnp.float32)
    state = state_ref[...]                            # (P, N)
    y += jnp.dot(
        cmat * jnp.exp(cum_c), state.T,
        preferred_element_type=jnp.float32,
    )
    y_ref[0, 0] = y.astype(y_ref.dtype)
    cum_last = cum_r[:, chunk - 1:]                   # (1, 1)
    decay = jnp.exp(cum_last - cum_c) * dt_c          # (L, 1)
    state_ref[...] = jnp.exp(cum_last) * state + jnp.dot(
        x.T, bmat * decay, preferred_element_type=jnp.float32
    )

    @pl.when(ic == n_c - 1)
    def _done():
        hf_ref[0, 0] = state_ref[...].astype(jnp.float32)


def _kernel_name(tuning_op: str, s: int) -> str:
    if tuning_op == "ssd_scan":
        return "repro_ssd_scan"
    return "repro_ssd_decode" if s == 1 else "repro_ssd_prefill_chunk"


@functools.partial(
    jax.jit, static_argnames=("chunk", "interpret", "tuning_op")
)
def ssd_scan_pallas(
    x: jax.Array,    # (B, S, H, P)
    dt: jax.Array,   # (B, S, H)
    A: jax.Array,    # (H,)
    B_: jax.Array,   # (B, S, 1, N)  — n_groups == 1
    C: jax.Array,    # (B, S, 1, N)
    *,
    chunk: int = 64,
    initial_state: Optional[jax.Array] = None,   # (B, H, P, N)
    interpret=None,
    tuning_op: str = "ssd_scan",
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y (B,S,H,P), final_state (B,H,P,N)).

    ``tuning_op`` names the tuning-table entry the chunk size resolves
    from: the training path tunes as ``"ssd_scan"``, the serving path
    (``ops.ssd_prefill_chunk``) as ``"ssd_prefill_chunk"`` — so serving's
    knob is never overridden by a training setting.  It names the kernel
    too, by phase, so a device trace splits SSD time as it splits paged
    attention's: ``repro_ssd_scan`` (training), ``repro_ssd_decode`` (a
    serving call of one token per row), ``repro_ssd_prefill_chunk``
    (a serving chunk)."""
    if interpret is None:
        interpret = interpret_default()
    b, s, h, p = x.shape
    if B_.shape[2] != 1:
        raise ValueError(
            f"pallas SSD kernel supports n_groups=1, got {B_.shape[2]}")
    n = B_.shape[3]
    t = get_tuning(tuning_op, key=shape_class(s=s), chunk=chunk)
    # a chunk longer than the sequence is identical math on pure padding
    # (dt pads with 0 = state no-op): clamp so short sequences — down to
    # the S=1 decode-as-C=1 case — never pay a full chunk of dead MXU work
    chunk = max(1, min(t["chunk"], s))
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B_ = jnp.pad(B_, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sp = x.shape[1]
    n_c = sp // chunk
    h0 = (
        initial_state
        if initial_state is not None
        else jnp.zeros((b, h, p, n), jnp.float32)
    )
    # head-major per-token scalars; cum restarts at every chunk boundary
    dt_h = dt.astype(jnp.float32).transpose(0, 2, 1)            # (B,H,S')
    cum_h = jnp.cumsum(
        (dt_h * A.astype(jnp.float32)[None, :, None]).reshape(
            b, h, n_c, chunk),
        axis=-1,
    ).reshape(b, h, sp)
    col = lambda v: v[..., None]                                # (B,H,S',1)
    row = lambda v: v.reshape(b, h, n_c, 1, chunk)
    grid = (b, h, n_c)
    col_spec = pl.BlockSpec((1, 1, chunk, 1), lambda b_, ih, ic: (b_, ih, ic, 0))
    row_spec = pl.BlockSpec(
        (1, 1, 1, 1, chunk), lambda b_, ih, ic: (b_, ih, ic, 0, 0))
    y, hf = pl.pallas_call(
        functools.partial(_ssd_kernel, n_c=n_c, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b_, ih, ic: (b_, ih, ic, 0)),
            col_spec,
            row_spec,
            col_spec,
            row_spec,
            pl.BlockSpec((1, chunk, n), lambda b_, ih, ic: (b_, ic, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, ih, ic: (b_, ic, 0)),
            pl.BlockSpec((1, 1, p, n), lambda b_, ih, ic: (b_, ih, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b_, ih, ic: (b_, ih, ic, 0)),
            pl.BlockSpec((1, 1, p, n), lambda b_, ih, ic: (b_, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sp, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[plc.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        compiler_params=plc.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        name=_kernel_name(tuning_op, s),
    )(
        x.transpose(0, 2, 1, 3),
        col(dt_h),
        row(dt_h),
        col(cum_h),
        row(cum_h),
        B_.reshape(b, sp, n),
        C.reshape(b, sp, n),
        h0,
    )
    return y[:, :, :s].transpose(0, 2, 1, 3), hf
