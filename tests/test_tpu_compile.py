"""Compile rehearsal for one TPU v5e chip: the Pallas kernels and the
full-width decode step compiled by the TPU compiler for a described (not
attached) v5e, which refuses what interpret mode lets through (block
shapes off the (8, 128) tiling, programs that do not fit the chip).

Nothing here runs on a chip; a compile that passes is not a chip run.  The
topology is described inside a fixture, never at import, so every worker
collects the same tests and only the one given this file loads the TPU
compiler.  The persistent compilation cache is off around the compiles: a
TPU executable written to it could not be read back on this host.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(sharding, tree):
    return jax.tree.map(lambda s: _sds(sharding, s.shape, s.dtype), tree)


def _compile_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_checksum_kernel_compiles(one_chip):
    from repro.kernels.checksum import TILE_COLS, TILE_ROWS, \
        checksum_words_pallas
    words = 64 * 2**20 // 4                       # one 64 MiB leaf
    _compile_kernel(
        lambda w, wt: checksum_words_pallas(w, wt, interpret=False),
        _sds(one_chip, (words // TILE_COLS, TILE_COLS), jnp.uint32),
        _sds(one_chip, (TILE_ROWS, TILE_COLS), jnp.uint32))


def test_quantize_kernels_compile(one_chip):
    from repro.kernels.quantize import GROUP, dequantize_pallas, \
        quantize_pallas
    n_groups = 64 * 2**20 // 4 // GROUP           # 64 MiB of float32
    _compile_kernel(lambda x: quantize_pallas(x, interpret=False),
                    _sds(one_chip, (n_groups, GROUP), jnp.float32))
    _compile_kernel(lambda q, s: dequantize_pallas(q, s, interpret=False),
                    _sds(one_chip, (n_groups, GROUP), jnp.int8),
                    _sds(one_chip, (n_groups, 1), jnp.float32))


def test_shard_pack_kernels_compile(one_chip):
    from repro.kernels.shard_pack import CELL_COLS, shard_pack_pallas, \
        shard_unpack_pallas
    width, cell_rows = 16, 2**16 // 4 // CELL_COLS   # 64 KiB cells
    n_cells = 64 * 2**20 // 2**16                    # 64 MiB
    _compile_kernel(lambda c: shard_pack_pallas(c, width, interpret=False),
                    _sds(one_chip, (n_cells, cell_rows, CELL_COLS),
                         jnp.uint32))
    _compile_kernel(lambda p: shard_unpack_pallas(p, interpret=False),
                    _sds(one_chip, (width, n_cells // width, cell_rows,
                                    CELL_COLS), jnp.uint32))


@pytest.mark.parametrize("group", [1, 4])
def test_flash_forward_compiles(one_chip, group):
    from repro.kernels.flash_attention import flash_fwd_pallas
    B, n_kv, S, D = 1, 2, 2048, 128
    kv = _sds(one_chip, (B, n_kv, S, D), jnp.bfloat16)
    _compile_kernel(lambda q, k, v: flash_fwd_pallas(q, k, v,
                                                     interpret=False),
                    _sds(one_chip, (B, n_kv, group, S, D), jnp.bfloat16),
                    kv, kv)


@pytest.mark.parametrize("group", [1, 4])
def test_flash_backward_compiles(one_chip, group):
    from repro.kernels.flash_attention import flash_bwd_pallas
    B, n_kv, S, D = 1, 2, 2048, 128
    q = _sds(one_chip, (B, n_kv, group, S, D), jnp.bfloat16)
    kv = _sds(one_chip, (B, n_kv, S, D), jnp.bfloat16)
    row = _sds(one_chip, (B, n_kv, group, S), jnp.float32)
    _compile_kernel(
        lambda q, k, v, do, lse, dlt: flash_bwd_pallas(
            q, k, v, do, lse, dlt, interpret=False),
        q, kv, kv, q, row, row)


def test_full_width_decode_step_fits_one_chip(one_chip):
    """h2o-danube-1.8b at its registry widths: the decode step over a
    4 x 2048 session cache compiles and fits one chip's HBM."""
    from repro.configs import get_arch
    from repro.models import cache_spec, param_shapes
    from repro.serve import make_decode_step
    cfg = get_arch("h2o-danube-1.8b")
    B, S = 4, 2048
    compiled = jax.jit(make_decode_step(cfg)).lower(
        _on(one_chip, param_shapes(cfg)),
        _on(one_chip, cache_spec(cfg, S, B)),
        _sds(one_chip, (B, 1), jnp.int32),
        _sds(one_chip, (), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES
