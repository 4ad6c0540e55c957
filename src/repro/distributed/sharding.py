"""Sharding hints + rules — GSPMD glue between model code and the mesh.

Model code calls ``shard(x, axes)`` with *logical* axis names; outside a
mesh context this is a no-op (CPU smoke tests), inside it becomes
``with_sharding_constraint`` so the same single-source model lowers for the
production mesh — the paper's portability switch, applied to distribution.

``param_sharding_rules`` maps parameter pytree paths to NamedShardings:
FSDP (ZeRO-3) over the ``data`` axis + tensor parallelism over ``model``.
"""
from __future__ import annotations

import contextlib
import re
import threading
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


class _MeshState(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.data_axes: Tuple[str, ...] = ("data",)
        self.model_axes: Tuple[str, ...] = ("model",)
        self.sequence_parallel: bool = False


_STATE = _MeshState()


@contextlib.contextmanager
def use_mesh(
    mesh: Mesh, *, data_axes=("data",), model_axes=("model",),
    sequence_parallel: bool = False,
):
    """Activate sharding hints. data_axes may include 'pod' for multi-pod DP.

    sequence_parallel: shard the residual stream's sequence dim over the
    model axis between blocks (Megatron-SP); cuts per-layer activation
    residency by the TP degree — essential for the 100-layer configs.
    """
    prev = (
        _STATE.mesh, _STATE.data_axes, _STATE.model_axes,
        _STATE.sequence_parallel,
    )
    _STATE.mesh, _STATE.data_axes, _STATE.model_axes = (
        mesh, tuple(data_axes), tuple(model_axes)
    )
    _STATE.sequence_parallel = sequence_parallel
    try:
        with mesh:
            yield
    finally:
        (_STATE.mesh, _STATE.data_axes, _STATE.model_axes,
         _STATE.sequence_parallel) = prev


def active_mesh() -> Optional[Mesh]:
    return _STATE.mesh


def _resolve(axis):
    """Map logical axis name -> physical mesh axes tuple."""
    if axis is None:
        return None
    if axis == "data":
        return _STATE.data_axes if len(_STATE.data_axes) > 1 else _STATE.data_axes[0]
    if axis == "model":
        return _STATE.model_axes if len(_STATE.model_axes) > 1 else _STATE.model_axes[0]
    if axis == "sp":  # sequence-parallel: model axis if enabled, else unsharded
        if not _STATE.sequence_parallel:
            return None
        return _resolve("model")
    return axis


def pspec(axes: Sequence[Optional[str]]) -> P:
    return P(*[_resolve(a) for a in axes])


def axis_size(logical: str) -> int:
    """Product of mesh axis sizes behind a logical axis (1 without a mesh)."""
    if _STATE.mesh is None:
        return 1
    phys = _resolve(logical)
    if phys is None:
        return 1
    if isinstance(phys, str):
        phys = (phys,)
    n = 1
    for a in phys:
        n *= _STATE.mesh.shape[a]
    return n


def shard(x: jax.Array, axes: Sequence[Optional[str]]) -> jax.Array:
    """Sharding hint; identity without an active mesh.

    Divisibility-aware: any logical axis that does not evenly divide the
    corresponding dim is dropped (avoids GSPMD involuntary-remat paths for
    e.g. 2 KV heads over a 16-way model axis).
    """
    if _STATE.mesh is None:
        return x
    if len(axes) != x.ndim:
        raise ValueError(f"axes {axes} vs rank {x.ndim}")
    eff = []
    for i, a in enumerate(axes):
        if a == "auto":  # explicitly leave the dim to GSPMD propagation
            eff.append(P.UNCONSTRAINED)
        elif a is None or x.shape[i] % max(axis_size(a), 1) == 0:
            eff.append(_resolve(a))
        else:
            # indivisible: leave the dim to GSPMD propagation
            eff.append(P.UNCONSTRAINED)
    return jax.lax.with_sharding_constraint(x, P(*eff))


# ---------------------------------------------------------------------------
# Parameter sharding rules (FSDP over data + TP over model)
# ---------------------------------------------------------------------------

# Matched in order against the '/'-joined param path; first hit wins.
# Convention: weights (in_dim, out_dim). TP shards the "wide" dim; FSDP
# shards the other over data.
_RULES = [
    # embeddings / lm head: vocab on model (TP vocab parallelism), d on data
    (r"embed", ("model", "data")),
    (r"lm_head", ("data", "model")),
    # attention
    (r"\bwq\b|\bwk\b|\bwv\b", ("data", "model")),
    (r"\bwo\b", ("model", "data")),
    (r"\bbq\b|\bbk\b|\bbv\b", ("model",)),
    # mlp
    (r"\bwg\b|\bwi\b", ("data", "model")),
    # moe experts have a leading E axis -> EP over model, FSDP over data
    (r"experts|moe", ("model", "data", None)),
    (r"router", ("data", None)),
    # mamba
    (r"w_in", ("data", "model")),
    (r"w_out", ("model", "data")),
    (r"conv_w", (None, "model")),
    (r"conv_b", ("model",)),
    # norms / scalars / small vectors: replicate
    (r"ln|gate|a_log|d_skip|dt_bias|\bb\b", None),
]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def spec_for_param(path, leaf) -> P:
    """PartitionSpec for one parameter leaf (stacked layer axes prepended)."""
    s = _path_str(path)
    ndim = getattr(leaf, "ndim", 0)
    for pat, axes in _RULES:
        if re.search(pat, s):
            if axes is None:
                return P()
            axes = [a for a in axes]
            # leading stacked-layer axes (scan over layers/groups): leave
            # unsharded; align the rule to the *trailing* dims
            extra = ndim - len(axes)
            if extra < 0:
                axes = axes[-ndim:] if ndim else []
            full = [None] * max(extra, 0) + list(axes)
            # drop shardings that would over-partition tiny dims
            return P(*[_resolve(a) for a in full])
    return P()


def params_pspecs(params):
    """Pytree of PartitionSpecs matching the params pytree."""
    return jax.tree_util.tree_map_with_path(spec_for_param, params)


def params_shardings(mesh: Mesh, params):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, spec_for_param(path, leaf)),
        params,
    )
