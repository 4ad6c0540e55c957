"""The work that traffic asks of each layer: operations and bytes.

Every function here takes the configuration's sizes and the tokens that
requests fed, never a kernel's grid, block or padding, so two kernels that
do the same job are judged on the same work.  A token at position ``t``
of its request (0-based) attends ``t + 1`` positions: the prompt and the
tokens before it, itself included.

Sizes are counted as served: weights and activations in the
configuration's dtype.
"""
from __future__ import annotations

from dataclasses import dataclass

from arch import Arch

ITEM = {"bfloat16": 2, "float32": 4}


@dataclass
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __iadd__(self, other: "Work") -> "Work":
        self.flops += other.flops
        self.bytes += other.bytes
        return self

    def bound_s(self, peak_flops: float, peak_bw: float) -> tuple[float, str]:
        """Least time the chip could take, and which peak bounds it."""
        t_c, t_m = self.flops / peak_flops, self.bytes / peak_bw
        return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


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
            * ITEM[arch.dtype])


def ctx_sum(first: int, last: int) -> int:
    """Sum of contexts (t + 1) over positions t in [first, last)."""
    return (last * (last + 1) - first * (first + 1)) // 2


def attention(arch: Arch, first: int, last: int, *, chunk: int) -> Work:
    """Attention over positions [first, last) of one request, fed in
    pieces of ``chunk`` positions (1 for decode): 4 * ctx * Hq * hd
    operations per token and layer (q.k and p.v), and the live K and V
    of the context read once per piece."""
    if last <= first:
        return Work()
    flops = 4.0 * ctx_sum(first, last) * arch.n_heads * arch.head_dim \
        * arch.n_layers
    nbytes = 0.0
    for start in range(first, last, chunk):
        end = min(start + chunk, last)
        nbytes += end * kv_bytes_per_token(arch)
    return Work(flops, nbytes)


def gemm(arch: Arch, tokens: int, head_tokens: int, dispatches: int) -> Work:
    """Projections: 2 * tokens * weights operations.  Bytes: the weights
    read once per dispatch, and every token's input and output rows."""
    item = ITEM[arch.dtype]
    flops = 2.0 * (tokens * matmul_params(arch) + head_tokens * head_params(arch))
    w_bytes = (matmul_params(arch) + head_params(arch)) * item * dispatches
    d, h, hkv, hd, ff = (arch.d_model, arch.n_heads, arch.n_kv_heads,
                         arch.head_dim, arch.d_ff)
    rows = (d + h * hd) + (d + 2 * hkv * hd) + (h * hd + d) \
        + 2 * (d + ff) + (ff + d)
    act = (tokens * rows * arch.n_layers + head_tokens * (d + arch.vocab)) \
        * item
    return Work(flops, w_bytes + act)


def model_flops(arch: Arch, tokens: int, head_tokens: int,
                attn_ctx: int) -> float:
    """Operations the model needs: projections, the head on the positions
    whose logits are used, and attention over ``attn_ctx`` summed
    contexts."""
    f = 2.0 * (tokens * matmul_params(arch) + head_tokens * head_params(arch))
    return f + 4.0 * attn_ctx * arch.n_heads * arch.head_dim * arch.n_layers
