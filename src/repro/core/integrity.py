"""End-to-end data integrity — DAOS checksums, TPU-adapted.

DAOS computes a checksum client-side on update, stores it with the extent, and
verifies on fetch (end-to-end: detects corruption anywhere on the path).  We
use a positional weighted checksum over uint32 words:

    csum(x) = ( sum_i  W^(i+1) * x_i  mod 2^32 )  xor  mix(len)

with W = 2654435761 (Knuth's multiplicative constant).  Positional weights make
it order-sensitive (unlike a plain sum) and the form is *tile-decomposable*:

    csum = sum_t  W^(t*T) * csum_tile(x_t)

which is exactly what the Pallas kernel in ``repro.kernels.checksum`` exploits
to compute it on-device with (8,128) VMEM tiles.  This module is the host-side
numpy implementation and uses the same decomposition with T = 2^18 words (one
1 MiB stripe cell): the weights W^1..W^T are built once, at import, and every
input of any length is summed against them a block at a time.
``tests/test_kernels.py`` asserts all three (numpy, ref.py jnp oracle, Pallas
interpret) agree bit-for-bit.
"""
from __future__ import annotations

import numpy as np

from . import obs

WEIGHT = np.uint32(2654435761)
_MASK32 = (1 << 32) - 1
_MASK64 = (1 << 64) - 1


def _splitmix64(x: int) -> int:
    x = (x + 0x9E3779B97F4A7C15) & _MASK64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4B5B9) & _MASK64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _MASK64
    return x ^ (x >> 31)


def _as_u32_words(data) -> tuple[np.ndarray, int, int]:
    """View arbitrary bytes as little-endian uint32 words: the whole words,
    the last 1-3 bytes zero padded into one more word (0 if none), and the
    byte length."""
    if isinstance(data, np.ndarray):
        buf = np.ascontiguousarray(data).view(np.uint8).reshape(-1)
    else:
        buf = np.frombuffer(bytes(data), dtype=np.uint8)
    n = buf.size
    whole = n - n % 4
    tail = int.from_bytes(buf[whole:].tobytes(), "little")
    return buf[:whole].view("<u4"), tail, n


def weight_powers(n: int, start_power: int = 1) -> np.ndarray:
    """W^(start_power), W^(start_power+1), ..., length n, as uint32."""
    if n == 0:
        return np.zeros(0, np.uint32)
    out = np.empty(n, np.uint32)
    w = pow(int(WEIGHT), start_power, 1 << 32)
    out[0] = w
    if n > 1:
        # cumulative product with natural uint32 wraparound
        np.multiply.accumulate(
            np.concatenate([[np.uint32(w)], np.full(n - 1, WEIGHT)]),
            out=out, dtype=np.uint32)
    return out


BLOCK_WORDS = 1 << 18
# W^1..W^T, built once and never written after: concurrent callers (the
# checkpointer's save threads) only read it.
_TABLE = weight_powers(BLOCK_WORDS)
_TABLE.flags.writeable = False
_W_BLOCK = pow(int(WEIGHT), BLOCK_WORDS, 1 << 32)


def _weighted_sum(words: np.ndarray) -> int:
    """sum_i W^(i+1) * words[i] mod 2^32, one block of T words at a time:
    block b adds W^(b*T) * sum(block_b * W^1..W^T)."""
    scratch = np.empty(min(words.size, BLOCK_WORDS), np.uint32)
    acc, shift = 0, 1
    for lo in range(0, words.size, BLOCK_WORDS):
        block = words[lo:lo + BLOCK_WORDS]
        part = np.multiply(block, _TABLE[:block.size],
                           out=scratch[:block.size])
        acc = (acc + shift * int(part.sum(dtype=np.uint32))) & _MASK32
        shift = (shift * _W_BLOCK) & _MASK32
    return acc


def checksum(data) -> int:
    """Weighted-word checksum of a bytes-like / ndarray. Returns python int."""
    with obs.span("integrity.checksum") as sp:
        words, tail, nbytes = _as_u32_words(data)
        sp["nbytes"] = nbytes
        acc = _weighted_sum(words)
        if tail:
            acc = (acc + pow(int(WEIGHT), words.size + 1, 1 << 32) * tail) \
                & _MASK32
    return acc ^ (_splitmix64(nbytes) & 0xFFFFFFFF)


class ChecksumError(IOError):
    """End-to-end integrity violation: stored checksum != recomputed."""

    def __init__(self, where: str, expected: int, got: int):
        super().__init__(
            f"checksum mismatch at {where}: stored={expected:#010x} "
            f"computed={got:#010x}")
        self.where, self.expected, self.got = where, expected, got


def verify(data, expected: int, where: str = "?") -> None:
    got = checksum(data)
    if got != expected:
        raise ChecksumError(where, expected, got)
