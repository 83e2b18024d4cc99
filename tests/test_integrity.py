"""The host checksum (``repro.core.integrity.checksum``) against plain
references, its pinned values, and its order sensitivity across the
1 MiB blocks it sums one at a time.

Guarantees pinned here:

* **same value** — for every length and input kind, the blocked sum equals
  the docstring's formula (Python ints for small inputs, one whole-array
  numpy product for large ones);
* **stable** — two seeded inputs keep the values computed before the
  blocked sum, so manifests and engine records written then still verify;
* **order sensitive** — swapping two whole blocks or flipping one byte in
  a later block changes the value, and a session whose second cell has
  one byte changed fails its restore.
"""
import ml_dtypes
import numpy as np
import pytest

from repro.core import ChecksumError, integrity
from repro.serve import KVCacheStore, KVStoreError

W = 2654435761
T = integrity.BLOCK_WORDS          # words per block
CELL = 4 * T                       # bytes per block: one 1 MiB stripe cell
MIB = 1 << 20


def _length_mix(nbytes: int) -> int:
    m = (1 << 64) - 1
    x = (nbytes + 0x9E3779B97F4A7C15) & m
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4B5B9) & m
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & m
    return (x ^ (x >> 31)) & 0xFFFFFFFF


def _words(data: bytes) -> np.ndarray:
    pad = (-len(data)) % 4
    return np.frombuffer(data + bytes(pad), "<u4")


def reference_checksum(data: bytes) -> int:
    """The docstring formula: Python ints for small inputs, one whole-array
    product against W^1..W^n for large ones."""
    words = _words(data)
    if len(data) <= 4096:
        acc = sum(pow(W, i + 1, 1 << 32) * int(x)
                  for i, x in enumerate(words)) & 0xFFFFFFFF
    else:
        powers = np.multiply.accumulate(np.full(words.size, W, np.uint32),
                                        dtype=np.uint32)
        acc = int(np.sum(powers * words, dtype=np.uint32))
    return acc ^ _length_mix(len(data))


LENGTHS = [0, 1, 2, 3, 4, CELL - 1, CELL, CELL + 1, CELL + 6, 3 * CELL + 5]
KINDS = ["bytes", "bytearray", "memoryview", "uint8", "noncontiguous",
         "bfloat16"]


def _as_kind(data: bytes, kind: str):
    if kind == "bytes":
        return data
    if kind == "bytearray":
        return bytearray(data)
    if kind == "memoryview":
        return memoryview(data)
    if kind == "uint8":
        return np.frombuffer(data, np.uint8)
    if kind == "noncontiguous":
        # every other byte of a twice-as-long array: a strided view
        spread = np.zeros(2 * len(data), np.uint8)
        spread[::2] = np.frombuffer(data, np.uint8)
        view = spread[::2]
        assert len(data) < 2 or not view.flags.c_contiguous
        return view
    if kind == "bfloat16":
        return np.frombuffer(data, ml_dtypes.bfloat16)
    raise ValueError(kind)


@pytest.mark.parametrize("kind,nbytes", [
    (k, n) for n in LENGTHS for k in KINDS
    if not (k == "bfloat16" and n % 2)])
def test_checksum_matches_reference(kind, nbytes):
    data = np.random.default_rng(nbytes).integers(
        0, 256, nbytes, np.uint8).tobytes()
    assert integrity.checksum(_as_kind(data, kind)) == \
        reference_checksum(data)


# Computed by the whole-array checksum that preceded the blocked sum.
KNOWN = [(1400, 300_001, 0xE19CF10E),
         (1401, 3 * MIB + 4 * 1000 + 3, 0xD93133F5)]


@pytest.mark.parametrize("seed,nbytes,want", KNOWN)
def test_checksum_known_answer(seed, nbytes, want):
    data = np.random.default_rng(seed).integers(
        0, 256, nbytes, np.uint8).tobytes()
    assert integrity.checksum(data) == want
    integrity.verify(data, want, where="written-before")


def test_checksum_order_sensitive_across_blocks():
    data = np.random.default_rng(7).integers(0, 256, 3 * CELL, np.uint8)
    base = integrity.checksum(data)
    swapped = np.concatenate([data[CELL:2 * CELL], data[:CELL],
                              data[2 * CELL:]])
    assert integrity.checksum(swapped) != base
    flipped = data.copy()
    flipped[CELL + 12345] ^= 0x01
    assert integrity.checksum(flipped) != base


def _two_cell_session(world):
    pool, dfs = world
    store = KVCacheStore(dfs, interface="dfs")
    rng = np.random.default_rng(3)
    cache = {"k": rng.integers(0, 256, 2 * MIB + 100, np.uint8),
             "v": rng.integers(0, 256, 2 * MIB + 100, np.uint8)}
    store.offload("s", cache, step=0)
    entry = store.manifest("s")["leaves"]["/k"]
    return pool, dfs, store, entry


def test_restore_detects_change_in_second_cell(world):
    _, _, store, entry = _two_cell_session(world)
    h = store.iface.open(entry["file"])
    pos = CELL + 777
    old = np.asarray(h.read_at(pos, 1))
    h.write_at(pos, old ^ np.uint8(0x80))       # out-of-band, one byte
    with pytest.raises(KVStoreError, match="checksum mismatch"):
        store.restore("s")


def test_engine_detects_change_in_second_cell(world):
    pool, dfs, store, entry = _two_cell_session(world)
    obj = dfs.open_file(entry["file"])
    assert obj.stripe_cell == CELL
    lay = obj._layout()
    cell = 1
    eng = pool.engines[lay.shard_for_chunk(cell)]
    versions = eng._store[(dfs.cont.label, obj.oid, "arr", cell)]
    rec = versions[max(versions)]
    buf = bytearray(rec.data)
    buf[777] ^= 0x80                            # behind the engine's api
    rec.data = bytes(buf)
    with pytest.raises(ChecksumError):
        store.restore("s")
