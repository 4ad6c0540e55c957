"""Sizes of a configuration file, under one set of names.

``configs/<name>.json`` keeps the keys of the model's published
``config.json``; this module reads them into :class:`Arch`, which the
weight maker, the reference and the work functions share.  It imports
nothing of the program under test.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent


@dataclass(frozen=True)
class Arch:
    """A dense decoder: grouped-query attention and a SwiGLU MLP."""
    n_layers: int
    d_model: int
    vocab: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    rope_theta: float
    qkv_bias: bool
    eps: float
    dtype: str             # weights and activations as served


def load_config(name: str, root: Path = HERE) -> dict:
    path = root / "configs" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration {name!r} at {path}")
    return json.loads(path.read_text())


def arch_of(cfg: dict) -> Arch:
    """The :class:`Arch` of a configuration file."""
    if cfg["family"] != "dense":
        raise ValueError(f"unknown family {cfg['family']!r}")
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return Arch(
        n_layers=cfg["num_hidden_layers"], d_model=d, vocab=cfg["vocab_size"],
        n_heads=h, n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim", d // h), d_ff=cfg["intermediate_size"],
        rope_theta=cfg["rope_theta"], qkv_bias=True, eps=cfg["rms_norm_eps"],
        dtype=cfg["torch_dtype"])
