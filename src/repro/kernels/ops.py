"""Public jit'd wrappers around the Pallas kernels.

These own all the padding/reshaping so the kernels only ever see aligned
tiles, and they pick the mode from the backend: compiled on TPU, interpret
mode on CPU (the tests), and an error on any other backend rather than a
silent interpreter run.  The host-side entry points (``checksum_array``) reproduce
``repro.core.integrity.checksum`` exactly, including the length mix.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .checksum import TILE, TILE_COLS, TILE_ROWS, checksum_words_pallas
from .quantize import BLOCK_GROUPS, GROUP, dequantize_pallas, quantize_pallas
from .shard_pack import CELL_COLS, shard_pack_pallas, shard_unpack_pallas

_MASK64 = (1 << 64) - 1


def interpret_mode() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels compile for TPU and interpret on "
                       f"CPU; the {backend!r} backend has neither")


def _splitmix64(x: int) -> int:
    x = (x + 0x9E3779B97F4A7C15) & _MASK64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4B5B9) & _MASK64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _MASK64
    return x ^ (x >> 31)


@functools.lru_cache(maxsize=4)
def _weights_tile() -> np.ndarray:
    return np.asarray(ref.weight_powers(TILE)).reshape(TILE_ROWS, TILE_COLS)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _checksum_words_device(words: jnp.ndarray, weights: jnp.ndarray,
                           interpret: bool = True) -> jnp.ndarray:
    n = words.shape[0]
    pad = (-n) % TILE
    if pad:
        words = jnp.concatenate([words, jnp.zeros(pad, jnp.uint32)])
    n_tiles = words.shape[0] // TILE
    lanes = checksum_words_pallas(
        words.reshape(n_tiles * TILE_ROWS, TILE_COLS), weights,
        interpret=interpret)
    return jnp.sum(lanes, dtype=jnp.uint32)


def checksum_array(x, interpret: bool | None = None) -> int:
    """Device-side checksum of any array; bit-identical to
    ``repro.core.integrity.checksum`` of the array's bytes."""
    interpret = interpret_mode() if interpret is None else interpret
    arr = np.ascontiguousarray(np.asarray(x))
    nbytes = arr.nbytes
    if nbytes == 0:
        return 0 ^ (_splitmix64(0) & 0xFFFFFFFF)
    u8 = jnp.asarray(arr.view(np.uint8).reshape(-1))
    words = ref.bytes_to_words(u8)
    acc = int(_checksum_words_device(words, _weights_tile(),
                                     interpret=interpret))
    return acc ^ (_splitmix64(nbytes) & 0xFFFFFFFF)


# ----------------------------- quantisation -----------------------------

@functools.partial(jax.jit, static_argnames=("interpret",))
def _quant_groups(flat: jnp.ndarray, interpret: bool = True):
    n = flat.shape[0]
    pad = (-n) % (GROUP * BLOCK_GROUPS)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros(pad, jnp.float32)])
    return quantize_pallas(flat.reshape(-1, GROUP), interpret=interpret)


def quantize(x: jnp.ndarray, interpret: bool | None = None):
    """-> (q int8 [n_groups, GROUP], scales [n_groups, 1], meta) where meta
    carries the original shape/dtype/length for dequantize()."""
    interpret = interpret_mode() if interpret is None else interpret
    meta = (x.shape, x.dtype, int(np.prod(x.shape)) if x.shape else 1)
    q, s = _quant_groups(jnp.asarray(x, jnp.float32).reshape(-1),
                         interpret=interpret)
    return q, s, meta


@functools.partial(jax.jit, static_argnames=("interpret",))
def _dequant_groups(q: jnp.ndarray, s: jnp.ndarray, interpret: bool = True):
    return dequantize_pallas(q, s, interpret=interpret)


def dequantize(q: jnp.ndarray, scales: jnp.ndarray, meta,
               interpret: bool | None = None) -> jnp.ndarray:
    interpret = interpret_mode() if interpret is None else interpret
    shape, dtype, n = meta
    flat = _dequant_groups(q, scales, interpret=interpret).reshape(-1)
    return flat[:n].reshape(shape).astype(dtype)


# ----------------------------- stripe packing -----------------------------

def shard_pack(x: jnp.ndarray, width: int, cell_bytes: int = 1 << 16,
               interpret: bool | None = None):
    """Reorder a flat byte buffer into per-target stripe buffers.

    -> (packed (width, cells_per_target, cell_rows, 128) uint32, meta).
    cell_bytes must be a multiple of 512 (=128 lanes x 4 B).
    """
    interpret = interpret_mode() if interpret is None else interpret
    assert cell_bytes % (CELL_COLS * 4) == 0
    cell_words = cell_bytes // 4
    cell_rows = cell_words // CELL_COLS
    arr = np.ascontiguousarray(np.asarray(x))
    u8 = jnp.asarray(arr.view(np.uint8).reshape(-1))
    words = ref.bytes_to_words(u8)
    n = words.shape[0]
    pad = (-n) % (cell_words * width)
    if pad:
        words = jnp.concatenate([words, jnp.zeros(pad, jnp.uint32)])
    cells = words.reshape(-1, cell_rows, CELL_COLS)
    packed = shard_pack_pallas(cells, width, interpret=interpret)
    meta = (arr.nbytes, cell_bytes, width)
    return packed, meta


def shard_unpack(packed: jnp.ndarray, meta,
                 interpret: bool | None = None) -> np.ndarray:
    """Inverse: -> original raw bytes as np.uint8[orig_nbytes]."""
    interpret = interpret_mode() if interpret is None else interpret
    orig_nbytes, cell_bytes, width = meta
    cells = shard_unpack_pallas(packed, interpret=interpret)
    words = np.asarray(cells).reshape(-1).astype(np.uint32)
    u8 = words.view(np.uint8)  # little-endian round trip
    return u8[:orig_nbytes]


# ----------------------------- flash attention -----------------------------
# Model-facing wrapper over kernels/flash_attention.py: handles the
# (B,S,Hq,D) <-> (B,n_kv,G,S,D) layout, pads head_dim to 128, and provides
# the custom VJP (backward = the two Pallas backward kernels).

def _pad_d(x: jnp.ndarray) -> tuple[jnp.ndarray, int]:
    D = x.shape[-1]
    pad = (-D) % 128
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros(x.shape[:-1] + (pad,), x.dtype)], axis=-1)
    return x, D


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def pallas_flash_attention(q, k, v, n_kv: int, causal: bool = True,
                           window: int = 0, prefix: int = 0,
                           bq: int = 256, bk: int = 512):
    """q: (B,S,Hq,D); k,v: (B,Sk,n_kv,D) -> (B,S,Hq,D)."""
    out, _ = _pallas_flash_fwd(q, k, v, n_kv, causal, window, prefix, bq, bk)
    return out


def _to_kernel_layout(q, k, v, n_kv):
    B, S, Hq, D = q.shape
    G = Hq // n_kv
    q5 = q.reshape(B, S, n_kv, G, D).transpose(0, 2, 3, 1, 4)
    k4 = k.transpose(0, 2, 1, 3)
    v4 = v.transpose(0, 2, 1, 3)
    return q5, k4, v4, G


def _pallas_flash_fwd(q, k, v, n_kv, causal, window, prefix, bq, bk):
    from .flash_attention import flash_fwd_pallas
    B, S, Hq, D = q.shape
    q5, k4, v4, G = _to_kernel_layout(q, k, v, n_kv)
    q5, D0 = _pad_d(q5)
    k4, _ = _pad_d(k4)
    v4, _ = _pad_d(v4)
    out5, lse = flash_fwd_pallas(q5, k4, v4, causal=causal, window=window,
                                 prefix=prefix, bq=bq, bk=bk,
                                 scale=1.0 / float(np.sqrt(D0)),
                                 interpret=interpret_mode())
    out = out5[..., :D0].transpose(0, 3, 1, 2, 4).reshape(B, S, Hq, D0)
    return out, (q, k, v, out, lse)


def _pallas_flash_bwd(n_kv, causal, window, prefix, bq, bk, res, dout):
    from .flash_attention import flash_bwd_pallas
    q, k, v, out, lse = res
    B, S, Hq, D = q.shape
    q5, k4, v4, G = _to_kernel_layout(q, k, v, n_kv)
    do5 = dout.reshape(B, S, n_kv, G, D).transpose(0, 2, 3, 1, 4)
    o5 = out.reshape(B, S, n_kv, G, D).transpose(0, 2, 3, 1, 4)
    delta = jnp.sum(do5.astype(jnp.float32) * o5.astype(jnp.float32),
                    axis=-1)
    q5, D0 = _pad_d(q5)
    k4, _ = _pad_d(k4)
    v4, _ = _pad_d(v4)
    do5, _ = _pad_d(do5)
    dq5, dk4, dv4 = flash_bwd_pallas(q5, k4, v4, do5, lse, delta,
                                     causal=causal, window=window,
                                     prefix=prefix, bq=bq, bk=bk,
                                     scale=1.0 / float(np.sqrt(D)),
                                     interpret=interpret_mode())
    dq = dq5[..., :D0].transpose(0, 3, 1, 2, 4).reshape(B, S, Hq, D0) \
        .astype(q.dtype)
    dk = dk4[..., :D0].transpose(0, 2, 1, 3).astype(k.dtype)
    dv = dv4[..., :D0].transpose(0, 2, 1, 3).astype(v.dtype)
    return dq, dk, dv


pallas_flash_attention.defvjp(_pallas_flash_fwd, _pallas_flash_bwd)
