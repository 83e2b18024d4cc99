"""Per-kernel validation: shape/dtype sweeps against the ref.py oracles
(interpret mode executes the kernel bodies on CPU)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import integrity
from repro.kernels import ops, ref
from repro.kernels.checksum import checksum_words_pallas, TILE
from repro.kernels.quantize import quantize_pallas, dequantize_pallas, GROUP
from repro.kernels.shard_pack import shard_pack_pallas, shard_unpack_pallas

rng = np.random.default_rng(42)


# --------------------------- checksum ---------------------------

@pytest.mark.parametrize("nbytes", [0, 1, 3, 4, 5, 64, 1023, 4096, 4097,
                                    65536, 100_001])
def test_checksum_matches_host(nbytes):
    data = rng.integers(0, 256, nbytes, dtype=np.uint8)
    assert ops.checksum_array(data) == integrity.checksum(data.tobytes())


@pytest.mark.parametrize("dtype", [np.uint8, np.int32, np.float32,
                                   np.float16, np.float64])
def test_checksum_dtypes(dtype):
    if np.issubdtype(dtype, np.floating):
        a = rng.normal(size=(17, 33)).astype(dtype)
    else:
        a = rng.integers(0, 100, (17, 33)).astype(dtype)
    assert ops.checksum_array(a) == integrity.checksum(a)


def test_checksum_kernel_matches_jnp_ref():
    words = jnp.asarray(rng.integers(0, 2**32, 4 * TILE, dtype=np.uint32))
    expect = int(ref.checksum_words(words))
    n_tiles = 4
    weights = jnp.asarray(ops._weights_tile())
    lanes = checksum_words_pallas(words.reshape(n_tiles * 8, 128), weights)
    got = jnp.sum(lanes, dtype=jnp.uint32)
    assert int(got) == expect


def test_interpret_mode_follows_backend(monkeypatch):
    """Compiled on TPU, interpreted on CPU, refused anywhere else."""
    import jax
    for backend, want in (("tpu", False), ("cpu", True)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert ops.interpret_mode() is want
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops.interpret_mode()


def test_checksum_order_sensitive():
    a = np.arange(4096, dtype=np.uint8)
    b = a[::-1].copy()
    assert ops.checksum_array(a) != ops.checksum_array(b)


def test_checksum_detects_single_bit_flip():
    a = rng.integers(0, 256, 8192, dtype=np.uint8)
    b = a.copy()
    b[1234] ^= 1
    assert ops.checksum_array(a) != ops.checksum_array(b)


# --------------------------- quantize ---------------------------

@pytest.mark.parametrize("shape", [(8, GROUP), (64, GROUP)])
def test_quant_kernel_matches_ref(shape):
    x = jnp.asarray(rng.normal(0, 2, shape).astype(np.float32))
    qk, sk = quantize_pallas(x)
    qr, sr, _ = ref.quantize_int8(x, group=GROUP)
    np.testing.assert_array_equal(np.asarray(qk), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=1e-6)
    back_k = dequantize_pallas(qk, sk)
    back_r = ref.dequantize_int8(qr, sr, x.size).reshape(shape)
    np.testing.assert_allclose(np.asarray(back_k), np.asarray(back_r),
                               rtol=1e-6)


@pytest.mark.parametrize("shape", [(5,), (37, 513), (3, 7, 11),
                                   (1, GROUP * 8)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_quant_roundtrip_error_bound(shape, dtype):
    x = rng.normal(0, 3, shape).astype(dtype)
    q, s, meta = ops.quantize(x)
    x2 = ops.dequantize(q, s, meta)
    assert x2.shape == x.shape and x2.dtype == x.dtype
    scale_bound = np.abs(x.astype(np.float32)).max() / 127.0
    assert np.max(np.abs(x.astype(np.float32)
                         - np.asarray(x2, np.float32))) <= scale_bound * 1.02


def test_quant_zeros_stable():
    x = np.zeros((2, GROUP), np.float32)
    q, s, meta = ops.quantize(x)
    assert np.all(np.asarray(q) == 0)
    x2 = ops.dequantize(q, s, meta)
    assert np.all(np.asarray(x2) == 0)


# --------------------------- shard_pack ---------------------------

@pytest.mark.parametrize("width", [1, 2, 4, 16])
@pytest.mark.parametrize("n_cells_mult", [1, 3])
def test_shard_pack_kernel_matches_ref(width, n_cells_mult):
    n_cells = width * n_cells_mult
    cell_rows = 2
    cells = jnp.asarray(
        rng.integers(0, 2**32, (n_cells, cell_rows * 128), dtype=np.uint32))
    expect = ref.shard_pack(cells, width)
    got = shard_pack_pallas(cells.reshape(n_cells, cell_rows, 128), width)
    np.testing.assert_array_equal(
        np.asarray(expect).reshape(width, n_cells // width, cell_rows, 128),
        np.asarray(got))
    back = shard_unpack_pallas(got)
    np.testing.assert_array_equal(
        np.asarray(back).reshape(n_cells, cell_rows * 128),
        np.asarray(cells))


@pytest.mark.parametrize("nbytes,width,cell", [(123457, 4, 2048),
                                               (512, 1, 512),
                                               (1 << 20, 16, 65536)])
def test_shard_pack_roundtrip_bytes(nbytes, width, cell):
    data = rng.integers(0, 256, nbytes, dtype=np.uint8)
    packed, meta = ops.shard_pack(data, width=width, cell_bytes=cell)
    back = ops.shard_unpack(packed, meta)
    np.testing.assert_array_equal(back, data)
