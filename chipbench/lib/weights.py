"""Weights drawn from ``--seed``, on the device, in one jitted call.

The program and the plain reference both take their weights from here,
each from its own table of leaf paths and shapes, so the reference takes
nothing the program made.  A leaf's values depend only on the seed, its
path and its shape; the rule for its distribution comes from the last
component of its path.
"""
from __future__ import annotations

import functools
import zlib

import numpy as np

ONES = {"norm1", "norm2", "final_norm"}


def flatten(tree, prefix: str = "") -> dict:
    """Nested dicts -> {"/a/b": leaf}, keys sorted."""
    if isinstance(tree, dict):
        out = {}
        for k in sorted(tree):
            out.update(flatten(tree[k], f"{prefix}/{k}"))
        return out
    return {prefix: tree}


def unflatten(flat: dict) -> dict:
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        parts = path.strip("/").split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return out


def layout_of(tree) -> tuple:
    """((path, shape, dtype name), ...) of a pytree of arrays or specs."""
    return tuple((p, tuple(int(s) for s in x.shape), np.dtype(x.dtype).name)
                 for p, x in flatten(tree).items())


def _leaf(key, path: str, shape: tuple, dtype: str):
    import jax
    import jax.numpy as jnp
    name = path.rsplit("/", 1)[-1]
    f32 = jnp.float32
    if name in ONES:
        x = jnp.ones(shape, f32)
    elif name == "tok":
        x = jax.random.normal(key, shape, f32) * 0.02
    else:                           # (..., fan_in, fan_out) matrices
        x = jax.random.normal(key, shape, f32) / np.sqrt(shape[-2])
    return x.astype(dtype)


@functools.lru_cache(maxsize=4)
def _maker(layout: tuple):
    import jax

    def make(key):
        flat = {}
        for path, shape, dtype in layout:
            k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
            flat[path] = _leaf(k, path, shape, dtype)
        return flat
    return jax.jit(make)


def make(key, layout: tuple) -> dict:
    """The nested dict of device arrays for ``layout`` from ``key``."""
    return unflatten(_maker(layout)(key))
