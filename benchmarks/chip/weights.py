"""Random weights of a configuration, made by the benchmark from ``--seed``.

One jitted call draws every leaf on the device, in the dtype it is served
in, so no float32 copy of a stacked weight reaches device memory.  The
tree has the layout the program's models take (``embed``, ``ln_f`` and
``layers`` stacked over depth); the harness checks it against the
program's own parameter shapes before serving, so a layout change in the
program stops the run instead of serving wrong weights.

Scales: projections draw N(0, 1/fan_in); the (tied) embedding N(0, 0.02),
as Qwen2's initializer range; norm weights 1 + N(0, 0.1) and q/k/v biases
N(0, 0.1), so that neither is a no-op the check could not see.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from arch import Arch


def _dtype(arch: Arch):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[arch.dtype]


def shapes(arch: Arch) -> dict:
    """``{path: (shape, dtype, kind, scale)}`` of every leaf."""
    d, L, dt = arch.d_model, arch.n_layers, _dtype(arch)
    out = {
        "embed": ((arch.vocab, d), dt, "normal", 0.02),
        "ln_f": ((d,), dt, "norm", 0.1),
    }
    h, hkv, hd, ff = arch.n_heads, arch.n_kv_heads, arch.head_dim, arch.d_ff
    a, m = "layers/attn/", "layers/mlp/"
    out.update({
        a + "wq": ((L, d, h * hd), dt, "normal", d ** -0.5),
        a + "wk": ((L, d, hkv * hd), dt, "normal", d ** -0.5),
        a + "wv": ((L, d, hkv * hd), dt, "normal", d ** -0.5),
        a + "wo": ((L, h * hd, d), dt, "normal", (h * hd) ** -0.5),
        a + "bq": ((L, h * hd), dt, "normal", 0.1),
        a + "bk": ((L, hkv * hd), dt, "normal", 0.1),
        a + "bv": ((L, hkv * hd), dt, "normal", 0.1),
        a + "ln": ((L, d), dt, "norm", 0.1),
        m + "wg": ((L, d, ff), dt, "normal", d ** -0.5),
        m + "wi": ((L, d, ff), dt, "normal", d ** -0.5),
        m + "wo": ((L, ff, d), dt, "normal", ff ** -0.5),
        m + "ln": ((L, d), dt, "norm", 0.1),
    })
    return out


def _leaf(key, shape, dtype, kind, scale):
    if kind == "normal":
        return (jax.random.normal(key, shape) * scale).astype(dtype)
    if kind == "norm":
        return (1.0 + jax.random.normal(key, shape) * scale).astype(dtype)
    raise ValueError(kind)


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split("/")
        for k in parents:
            node = node.setdefault(k, {})
        node[last] = leaf
    return tree


@functools.partial(jax.jit, static_argnums=0)
def _make(arch: Arch, key):
    spec = shapes(arch)
    keys = jax.random.split(key, len(spec))
    return _nest({path: _leaf(k, *s)
                  for k, (path, s) in zip(keys, sorted(spec.items()))})


def make(arch: Arch, seed: int):
    """The parameter tree for ``seed`` (any non-negative integer)."""
    word = int(np.random.default_rng([int(seed), 2]).integers(0, 2 ** 31))
    return _make(arch, jax.random.PRNGKey(word))


def flat(tree) -> dict:
    """``{path: leaf}`` of a nested dict, paths joined by ``/``."""
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update({f"{k}/{p}": x for p, x in flat(v).items()})
        else:
            out[k] = v
    return out
