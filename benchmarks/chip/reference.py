"""What the plain reference forward passes share, and the gap they are
judged by.

Each family's ``logits(arch, params, tokens, positions, quantize="")``
(``families/<family>.py``), written from the layer equations, runs one
whole sequence in float32 at ``highest`` matmul precision, with no
kernel, cache, chunking or batching, and returns the logits at
``positions`` (the position that predicts token ``t + 1`` is ``t``).  It
imports nothing of the program: it reads the weights the benchmark made
(``weights.py``), casting each layer's leaves to float32 inside the scan
over depth, so it fits beside them on one chip.

``quantize`` is the control: every projection and the embedding rounded,
before use, to the precision below the one the configuration states:
``"fp8"`` (float8_e4m3fn, one scale per output channel) below bfloat16,
``"bf16"`` below float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

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


def gaps(ref_logits, tokens):
    """How far below the reference's best logit each chosen token lies."""
    picked = jnp.take_along_axis(ref_logits, tokens[:, None], axis=1)[:, 0]
    return jnp.max(ref_logits, axis=1) - picked
