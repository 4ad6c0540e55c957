"""The work that traffic asks of each layer: operations and bytes.

Each family's ``window_work`` (``families/<family>.py``) counts it with
the pieces here.  Every such count takes the configuration's sizes and
the tokens that requests fed, never a kernel's grid, block or padding, so
two kernels that do the same job are judged on the same work.  A token at
position ``t`` of its request (0-based) attends ``t + 1`` positions: the
prompt and the tokens before it, itself included.

Sizes are counted as served: weights and activations in the
configuration's dtype.
"""
from __future__ import annotations

from dataclasses import dataclass

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


def ctx_sum(first: int, last: int) -> int:
    """Sum of contexts (t + 1) over positions t in [first, last)."""
    return (last * (last + 1) - first * (first + 1)) // 2
