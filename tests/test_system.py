"""End-to-end behaviour tests: the full train -> fail -> recover -> resume
story, IOR claim validation, and serving consistency through the store."""
import argparse

import numpy as np
import pytest


def _train_args(**over):
    base = dict(arch="deepseek-7b", smoke=True, steps=18, batch=4, seq=48,
                vocab=128, interface="dfs", oclass="S2",
                ckpt_oclass="RP_2GX", ckpt_layout="sharded", ckpt_every=5,
                kill_at_step=0, grad_compression=False, servers=4, workers=4,
                corpus_tokens=60_000, shard_tokens=8192, seed=0)
    base.update(over)
    return argparse.Namespace(**base)


def test_train_end_to_end_loss_decreases():
    from repro.launch.train import run
    out = run(_train_args())
    assert out["steps"] == 18 and out["restarts"] == 0
    assert out["final_loss"] < out["first_loss"]


def test_train_survives_injected_failure():
    from repro.launch.train import run
    out = run(_train_args(kill_at_step=9, steps=16))
    assert out["restarts"] == 1
    assert out["steps"] == 16
    assert out["final_loss"] < out["first_loss"]


def test_train_device_error_is_not_recovered(monkeypatch):
    """Recovery is for the store's failures: a JAX runtime error (a device
    OOM, say) inside the loop ends the run instead of restoring."""
    import jax
    from repro.data import Prefetcher
    from repro.launch.train import run

    def failing_batches(self, batch, seq, seed=0):
        raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: injected")
        yield

    monkeypatch.setattr(Prefetcher, "batches", failing_batches)
    with pytest.raises(jax.errors.JaxRuntimeError, match="injected"):
        run(_train_args(steps=4))


def test_train_vocab_defaults_to_config():
    """Without --vocab the run keeps the config's vocabulary: the
    published one under --no-smoke, the smoke variant's 256 otherwise."""
    from repro.launch.train import model_config, parse_args
    full = model_config(parse_args(["--arch", "mamba2-370m", "--no-smoke"]))
    assert full.vocab_size == 50280 and full.d_model == 1024
    assert model_config(parse_args(["--arch", "mamba2-370m"])).vocab_size \
        == 256
    assert model_config(parse_args(["--vocab", "128"])).vocab_size == 128


def test_serve_bench_decode_measurement_failure_raises(monkeypatch):
    """No constant stands in for a decode cadence that failed to measure;
    --decode-ms stays the explicit pin."""
    import repro.serve
    from benchmarks import serve_bench

    def no_device(*a, **k):
        raise RuntimeError("no device")

    monkeypatch.setattr(repro.serve, "measure_decode_s", no_device)
    args = argparse.Namespace(decode_ms=0.0, decode_arch="deepseek-7b",
                              decode_batch=8, decode_iters=1)
    with pytest.raises(RuntimeError, match="no device"):
        serve_bench.resolve_decode_s(args)
    args.decode_ms = 2.0
    assert serve_bench.resolve_decode_s(args) == (2e-3, "forced")


def test_train_with_grad_compression():
    from repro.launch.train import run
    out = run(_train_args(steps=10, grad_compression=True))
    assert out["final_loss"] < out["first_loss"]


def test_train_shared_file_checkpoint_layout():
    from repro.launch.train import run
    out = run(_train_args(steps=8, ckpt_layout="shared"))
    assert out["final_loss"] < out["first_loss"]


def test_ior_claims_hold():
    """The paper's §IV findings (C1..C5) hold in the reproduction."""
    from benchmarks import ior
    rows = ior.main(["--clients", "1", "4", "16", "--out",
                     "/tmp/ior_test.json"])
    checks = ior.check_claims(rows)
    assert len(checks) == 5
    failed = [(n, d) for n, ok, d in checks if not ok]
    assert not failed, failed


def test_serving_consistency_after_ckpt_roundtrip():
    """Restored params must produce identical decode outputs."""
    import jax
    import jax.numpy as jnp
    from repro.configs import ARCHS, smoke_variant
    from repro.configs.base import ShapeConfig
    from repro.core import Pool, Topology
    from repro.core.interfaces import DFS
    from repro.ckpt import Checkpointer
    from repro.models import init_model, make_inputs
    from repro.serve import make_decode_step, make_prefill_step

    cfg = smoke_variant(ARCHS["chatglm3-6b"])
    key = jax.random.PRNGKey(1)
    params = init_model(key, cfg)

    pool = Pool(Topology(n_server_nodes=2, engines_per_node=2))
    dfs = DFS(pool.create_container("m", oclass="RP_2GX"))
    ck = Checkpointer(dfs, layout="sharded", n_writers=2)
    ck.save(0, params)
    restored = jax.tree.map(jnp.asarray, ck.restore(0, params))

    shape = ShapeConfig("s", 16, 2, "prefill")
    batch = make_inputs(key, cfg, shape)
    lg1, cache1 = make_prefill_step(cfg)(params, batch)
    lg2, cache2 = make_prefill_step(cfg)(restored, batch)
    np.testing.assert_array_equal(np.asarray(lg1), np.asarray(lg2))
    dec = make_decode_step(cfg)
    t1, d1, _ = dec(params, cache1, jnp.zeros((2, 1), jnp.int32),
                    jnp.asarray(15, jnp.int32))
    t2, d2, _ = dec(restored, cache2, jnp.zeros((2, 1), jnp.int32),
                    jnp.asarray(15, jnp.int32))
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
