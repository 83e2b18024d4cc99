"""Weights drawn from ``--seed``, on the device, in one jitted call.

The program and the plain reference both take their weights from here,
each from its own table of leaf paths and shapes, so the reference takes
nothing the program made.  A leaf's values depend only on the seed, its
path and its shape; the rule for its distribution comes from the last
component of its path: the family's own rule for that name, if its file
(``chipbench/families/<family>.py``) has one in ``LEAF_RULES`` (leaf
name -> ``f(key, shape)`` giving a float32 array), else the default rule
in ``RULES``, else the matrix rule.
"""
from __future__ import annotations

import functools
import pathlib
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from . import bench as B


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


def _ones(key, shape: tuple):
    return jnp.ones(shape, jnp.float32)


def _embedding(key, shape: tuple):
    return jax.random.normal(key, shape, jnp.float32) * 0.02


def _matrix(key, shape: tuple):
    """(..., fan_in, fan_out) matrices."""
    return jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[-2])


RULES = {"norm1": _ones, "norm2": _ones, "final_norm": _ones,
         "tok": _embedding}


@functools.lru_cache(maxsize=4)
def _maker(layout: tuple, family: str, bench_dir: pathlib.Path):
    table = {**RULES,
             **getattr(B.family(family, bench_dir), "LEAF_RULES", {})}

    def make(key):
        flat = {}
        for path, shape, dtype in layout:
            k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
            rule = table.get(path.rsplit("/", 1)[-1], _matrix)
            flat[path] = rule(k, shape).astype(dtype)
        return flat
    return jax.jit(make)


def make(key, layout: tuple, family: str,
         bench_dir: pathlib.Path = B.BENCH_DIR) -> dict:
    """The nested dict of device arrays for ``layout`` from ``key``, by
    the weight rules of ``family``."""
    return unflatten(_maker(layout, family, bench_dir)(key))
