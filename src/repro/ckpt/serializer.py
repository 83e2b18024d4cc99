"""Pytree <-> object-store serialisation.

A checkpoint is laid out the way the paper's IOR modes are:

* ``sharded`` (IOR *easy*, file-per-process): one object per host-shard of
  each leaf — the layout a 1000-host cluster writes, every host streaming
  its local shard concurrently;
* ``shared`` (IOR *hard*, single-shared-file): every leaf packed at an
  offset into ONE object; hosts write disjoint ranges.

Leaf bytes carry end-to-end checksums (computed with the Pallas kernel when
the leaf is a device array) stored in the manifest, verified on restore.
The manifest (tree structure, dtypes, shapes, offsets, checksums) is a KV
object written last, inside the same transaction — so a torn save is
invisible (no manifest at the committed epoch => checkpoint didn't happen).
"""
from __future__ import annotations

import json

import numpy as np

from ..core import integrity, obs
from ..kernels import ops as kops


def flatten_tree(tree, prefix=""):
    """-> list of (path, leaf). Stable, explicit, json-safe paths."""
    out = []
    if isinstance(tree, dict):
        for k in sorted(tree):
            out.extend(flatten_tree(tree[k], f"{prefix}/{k}"))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            out.extend(flatten_tree(v, f"{prefix}/{i}"))
    else:
        out.append((prefix or "/", tree))
    return out


def unflatten_tree(items: dict, template):
    return _unflatten_at(items, template, "")


def _unflatten_at(items, template, prefix):
    if isinstance(template, dict):
        return {k: _unflatten_at(items, template[k], f"{prefix}/{k}")
                for k in sorted(template)}
    if isinstance(template, (list, tuple)):
        vals = [_unflatten_at(items, v, f"{prefix}/{i}")
                for i, v in enumerate(template)]
        return type(template)(vals)
    return items[prefix or "/"]


def leaf_to_bytes(leaf) -> tuple[np.ndarray, dict]:
    """A leaf's bytes on the host (a device leaf is copied here) and its
    shape and dtype."""
    with obs.span("ser.to_host") as sp:
        arr = np.asarray(leaf)
        meta = {"shape": list(arr.shape), "dtype": str(arr.dtype)}
        raw = np.ascontiguousarray(arr).view(np.uint8).reshape(-1)
        sp["nbytes"] = int(raw.size)
    return raw, meta


def bytes_to_leaf(raw: np.ndarray, meta: dict):
    dtype = np.dtype(meta["dtype"])
    arr = raw[: int(np.prod(meta["shape"])) * dtype.itemsize] \
        .view(dtype).reshape(meta["shape"])
    return arr


def checksum_leaf(raw: np.ndarray, on_device: bool = False) -> int:
    if on_device:
        return kops.checksum_array(raw)
    return integrity.checksum(raw)


def shard_ranges(nbytes: int, n_shards: int) -> list[tuple[int, int]]:
    """Split a leaf's byte range across writer processes (hosts)."""
    per = -(-nbytes // max(1, n_shards))
    out = []
    for i in range(n_shards):
        lo = i * per
        hi = min(nbytes, lo + per)
        if lo >= hi:
            break
        out.append((lo, hi))
    return out


def manifest_dumps(entries: dict, extra: dict | None = None) -> bytes:
    return json.dumps({"leaves": entries, **(extra or {})},
                      sort_keys=True).encode()


def manifest_loads(raw: bytes) -> dict:
    return json.loads(raw.decode())
