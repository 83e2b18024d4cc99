"""Pallas TPU kernel: weighted uint32 checksum (end-to-end integrity).

DAOS checksums every extent client-side; at TPU speeds a multi-GiB
checkpoint shard would otherwise serialise on the host CPU.  The weighted
checksum (see ``repro.core.integrity``) is tile-decomposable:

    csum = sum_t  W^(t*T) * ( sum_j W^(j+1) * x[t*T + j] )

so each grid step multiplies one (8, 128) VMEM tile of uint32 words
(T = 1024) by a resident weight tile and the per-tile factor W^(t*T), and
adds the products lane-wise into an (8, 128) output that stays pinned
across the grid; the caller sums its 1024 lanes (mod 2^32, so the order of
the additions does not change the result).  The per-tile factor is a
running product in one SMEM word: the grid runs in order, so step t
multiplies it by W^T for step t+1 (a blocked per-tile scale vector would
need rank-1 blocks, which Mosaic refuses below 128).

TPU notes: (8, 128) is the float32/int32 native VREG tile; the multiply-add
runs on the VPU (integer path), no MXU involvement; the weight tile and the
accumulator live in VMEM for the whole sweep, so HBM traffic is exactly one
read of the data — the kernel is memory-bound by construction, which is the
roofline-optimal shape for a reduction.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import WEIGHT

TILE_ROWS = 8
TILE_COLS = 128
TILE = TILE_ROWS * TILE_COLS  # 1024 words per grid step
TILE_WEIGHT = pow(int(WEIGHT), TILE, 1 << 32)  # W^T: one tile's scale step


def _checksum_kernel(words_ref, weights_ref, out_ref, scale_ref):
    """One grid step: out += W^(t*T) * weights * words_tile (lane-wise)."""
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        scale_ref[0] = jnp.uint32(1)

    scale = scale_ref[0]
    out_ref[...] += scale * (weights_ref[...] * words_ref[...])
    scale_ref[0] = scale * jnp.uint32(TILE_WEIGHT)


def checksum_words_pallas(words: jnp.ndarray, weights: jnp.ndarray,
                          interpret: bool = True) -> jnp.ndarray:
    """words: (n_tiles*8, 128) uint32; weights: (8, 128) uint32 =
    W^1..W^1024 row-major. Returns the (8, 128) uint32 lane sums, whose
    total mod 2^32 is the weighted checksum of ``words``."""
    n_tiles = words.shape[0] // TILE_ROWS
    return pl.pallas_call(
        _checksum_kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((TILE_ROWS, TILE_COLS), lambda t: (t, 0)),  # words
            pl.BlockSpec((TILE_ROWS, TILE_COLS), lambda t: (0, 0)),  # weights
        ],
        out_specs=pl.BlockSpec((TILE_ROWS, TILE_COLS), lambda t: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((TILE_ROWS, TILE_COLS), jnp.uint32),
        scratch_shapes=[pltpu.SMEM((1,), jnp.uint32)],   # running W^(t*T)
        interpret=interpret,
    )(words, weights)
