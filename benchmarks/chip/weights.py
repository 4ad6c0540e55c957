"""Random weights of a configuration, made by the benchmark from ``--seed``.

The family's ``shapes(arch)`` lists every leaf as ``{path: (shape,
dtype, kind, scale)}``; one jitted call draws them all on the device, in
the dtype they are served in, so no float32 copy of a stacked weight
reaches device memory.  The tree has the layout the program's models take
(``embed``, ``ln_f`` and ``layers`` stacked over depth); the harness
checks it against the program's own parameter shapes before serving, so a
layout change in the program stops the run instead of serving wrong
weights.  Kinds: ``normal`` draws N(0, scale**2), ``norm`` 1 + N(0,
scale**2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def dtype_of(arch):
    """The dtype an ``Arch`` is served in."""
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[arch.dtype]


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


@functools.partial(jax.jit, static_argnums=(0, 1))
def _make(shapes, arch, key):
    spec = shapes(arch)
    keys = jax.random.split(key, len(spec))
    return _nest({path: _leaf(k, *s)
                  for k, (path, s) in zip(keys, sorted(spec.items()))})


def make(shapes, arch, seed: int):
    """The parameter tree of ``shapes(arch)`` for ``seed`` (any
    non-negative integer)."""
    word = int(np.random.default_rng([int(seed), 2]).integers(0, 2 ** 31))
    return _make(shapes, arch, jax.random.PRNGKey(word))


def flat(tree) -> dict:
    """``{path: leaf}`` of a nested dict, paths joined by ``/``."""
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update({f"{k}/{p}": x for p, x in flat(v).items()})
        else:
            out[k] = v
    return out
