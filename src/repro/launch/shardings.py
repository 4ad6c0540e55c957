"""Cell-specific sharding builders: (arch × shape × mesh) -> sharding pytrees.

Parameter rules come from ``repro.distributed.sharding`` with per-arch
overrides (e.g. MoE expert placement depends on whether n_experts divides
the model axis).  Serve-state rules are shape-aware: KV caches shard batch
over ``data`` when the batch is wide, and sequence over ``data`` (context
parallelism) for the single-sequence long_500k cell.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeSpec
from repro.distributed import sharding as S


def _ns(mesh: Mesh, *axes) -> NamedSharding:
    return NamedSharding(mesh, S.pspec(axes))


def _axes_size(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    if isinstance(entry, str):
        entry = (entry,)
    n = 1
    for a in entry:
        n *= mesh.shape[a]
    return n


def sanitize(mesh: Mesh, spec: P, shape) -> P:
    """pjit in_shardings demand divisibility; drop axes that don't divide."""
    ent = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, e in zip(shape, ent):
        out.append(e if dim % max(_axes_size(mesh, e), 1) == 0 else None)
    return P(*out)


def param_specs(cfg: ArchConfig, params_shape) -> "jax.tree":
    """PartitionSpec pytree for a params (or ShapeDtypeStruct) pytree."""
    moe_ep = cfg.n_experts and cfg.n_experts % 16 == 0

    def spec(path, leaf):
        s = S._path_str(path)
        ndim = getattr(leaf, "ndim", len(getattr(leaf, "shape", ())))
        if any(k in s for k in ("wg", "wi", "wo", "router")) and cfg.n_experts:
            # MoE expert tensors carry a leading E axis (after layer stacking)
            if "router" in s:
                return S.pspec([None] * (ndim - 2) + ["data", None])
            if moe_ep:
                # EP over model; FSDP over the NON-contracted (output) dim —
                # sharding the contracted d over data forced activation-sized
                # partial-sum all-reduces (perf iteration M3, §Perf)
                return S.pspec([None] * (ndim - 3) + ["model", None, "data"])
            # TP fallback: ff over model, d over data, experts replicated
            if s.endswith("wo") and ndim >= 3 and cfg.family == "moe":
                return S.pspec([None] * (ndim - 3) + [None, "model", "data"])
            if ndim >= 3 and cfg.family == "moe":
                return S.pspec([None] * (ndim - 3) + [None, "data", "model"])
        return S.spec_for_param(path, leaf)

    return jax.tree_util.tree_map_with_path(spec, params_shape)


def param_shardings(mesh: Mesh, cfg: ArchConfig, params_shape):
    specs = param_specs(cfg, params_shape)
    return jax.tree.map(
        lambda sp, leaf: NamedSharding(mesh, sanitize(mesh, sp, leaf.shape)),
        specs,
        params_shape,
        is_leaf=lambda x: isinstance(x, P),
    )


def opt_state_shardings(mesh: Mesh, cfg: ArchConfig, opt_shape):
    """Optimizer state mirrors the param shardings; the step is replicated."""
    out = {}
    for k, sub in opt_shape.items():
        if k == "step":
            out[k] = NamedSharding(mesh, P())
        else:
            out[k] = param_shardings(mesh, cfg, sub)
    return out


def batch_shardings(mesh: Mesh, cfg: ArchConfig, batch_shape):
    """Training/prefill batch: leading batch dim over the data axes."""
    def spec(path, leaf):
        nd = len(leaf.shape)
        if nd == 0:
            return NamedSharding(mesh, P())
        sp = S.pspec(["data"] + [None] * (nd - 1))
        return NamedSharding(mesh, sanitize(mesh, sp, leaf.shape))

    return jax.tree_util.tree_map_with_path(spec, batch_shape)


def decode_state_shardings(
    mesh: Mesh, cfg: ArchConfig, shape: ShapeSpec, state_shape
):
    """Serve caches. batch >= data-axis size -> batch-sharded; else the
    long-context cell shards the sequence/state dims (context parallelism)."""
    data_size = 1
    for a in mesh.axis_names:
        if a in ("pod", "data"):
            data_size *= mesh.shape[a]
    wide_batch = shape.global_batch >= data_size

    def spec(path, leaf):
        s = S._path_str(path)
        nd = len(leaf.shape)
        if nd == 0:
            return NamedSharding(mesh, P())
        if s.startswith("pos"):
            return NamedSharding(mesh, P())
        if s in ("k", "v", "xk", "xv"):
            # (..., B, S, Hkv, hd): last two dims are heads/hd
            axes = [None] * nd
            b_ix, s_ix, h_ix = nd - 4, nd - 3, nd - 2
            tp = mesh.shape.get("model", 1)
            if cfg.n_kv_heads and cfg.n_kv_heads % tp == 0:
                axes[h_ix] = "model"
            elif leaf.shape[s_ix] % tp == 0:
                # GQA kv-heads < TP degree: context-parallel cache — shard
                # the sequence over the model axis instead (flash-decoding
                # style partial-softmax combine; perf iteration D1, §Perf)
                axes[s_ix] = "model"
            if wide_batch:
                axes[b_ix] = "data"
            elif axes[s_ix] is None and leaf.shape[s_ix] % 16 == 0:
                axes[s_ix] = "data"       # context-parallel over data too
            return NamedSharding(mesh, S.pspec(axes))
        if s == "ssm":
            # (L, B, H, P, N)
            axes = [None, "data" if wide_batch else None, "model", None, None]
            if cfg.ssm_heads % 16 != 0:
                axes[2] = None
            return NamedSharding(mesh, S.pspec(axes))
        if s == "conv":
            # (L, B, K-1, conv_dim)
            axes = [None, "data" if wide_batch else None, None,
                    "model" if cfg.conv_dim % 16 == 0 else None]
            return NamedSharding(mesh, S.pspec(axes))
        return NamedSharding(mesh, P())

    def sane(path, leaf):
        ns = spec(path, leaf)
        return NamedSharding(mesh, sanitize(mesh, ns.spec, leaf.shape))

    return jax.tree_util.tree_map_with_path(sane, state_shape)


def token_shardings(mesh: Mesh, cfg: ArchConfig, shape: ShapeSpec):
    data_size = 1
    for a in mesh.axis_names:
        if a in ("pod", "data"):
            data_size *= mesh.shape[a]
    if shape.global_batch >= data_size:
        return NamedSharding(mesh, S.pspec(["data"]))
    return NamedSharding(mesh, P())
