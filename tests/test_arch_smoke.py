"""Per-architecture smoke tests (the brief's required reduced-config suite):
one forward/train step + one prefill/decode step on CPU for every assigned
arch, asserting output shapes and finiteness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES, shape_applicable, smoke_variant
from repro.configs.base import ShapeConfig
from repro.models import init_model, make_inputs, forward_train, param_count
from repro.serve import make_decode_step, make_prefill_step
from repro.train import make_train_step, opt_init

TRAIN = ShapeConfig("smoke_train", 32, 2, "train")
PREFILL = ShapeConfig("smoke_prefill", 32, 2, "prefill")
KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_forward_shapes_finite(arch):
    cfg = smoke_variant(ARCHS[arch])
    params = init_model(KEY, cfg)
    assert param_count(params) > 0
    batch = make_inputs(KEY, cfg, TRAIN)
    hidden, aux = forward_train(params, cfg, batch)
    B = TRAIN.global_batch
    from repro.models import text_len
    S_expect = text_len(cfg, TRAIN.seq_len) + (
        cfg.n_prefix_tokens if cfg.family == "vlm" else 0)
    assert hidden.shape == (B, S_expect, cfg.d_model)
    assert np.isfinite(np.asarray(hidden, np.float32)).all()
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_train_step_reduces_loss(arch):
    cfg = smoke_variant(ARCHS[arch])
    params = init_model(KEY, cfg)
    opt = opt_init(cfg.optimizer, params)
    step = jax.jit(make_train_step(cfg))
    batch = make_inputs(KEY, cfg, TRAIN)
    first = None
    for _ in range(4):
        params, opt, m = step(params, opt, batch)
        if first is None:
            first = float(m["loss"])
    assert np.isfinite(float(m["loss"]))
    assert float(m["loss"]) < first


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_prefill_decode_shapes(arch):
    cfg = smoke_variant(ARCHS[arch])
    params = init_model(KEY, cfg)
    batch = make_inputs(KEY, cfg, PREFILL)
    logits, cache = make_prefill_step(cfg)(params, batch)
    assert logits.shape[-1] == cfg.padded_vocab()
    nt, lg, cache2 = make_decode_step(cfg)(
        params, cache, jnp.zeros((2, 1), jnp.int32),
        jnp.asarray(PREFILL.seq_len - 1, jnp.int32))
    assert lg.shape[:2] == (2, 1)
    assert np.isfinite(np.asarray(lg, np.float32)).all()
    assert jax.tree.structure(cache) == jax.tree.structure(cache2)


def test_applicability_matrix():
    """44 cells; long_500k runs only for sub-quadratic archs."""
    cells = [(a, s) for a in ARCHS for s in SHAPES]
    assert len(cells) == 44
    runnable = [(a, s) for a, s in cells
                if shape_applicable(ARCHS[a], SHAPES[s])[0]]
    assert len(runnable) == 36
    skipped = sorted(set(cells) - set(runnable))
    assert all(s == "long_500k" for _, s in skipped)
    subq = {a for a, s in runnable if s == "long_500k"}
    assert subq == {"h2o-danube-1.8b", "recurrentgemma-9b", "mamba2-370m"}
