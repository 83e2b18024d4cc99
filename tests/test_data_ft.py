"""Data pipeline (prefetch, stragglers, loss tolerance) + failure detection
+ elastic replanning + hlo cost analyzer."""
import numpy as np
import pytest

from repro.core import Pool, Topology
from repro.core.interfaces import DFS
from repro.data import (ObjectStoreDataset, Prefetcher, synthetic_corpus,
                        write_corpus)
from repro.ft import FailureDetector, replan_data_parallel


@pytest.fixture()
def world():
    pool = Pool(Topology(n_server_nodes=4, engines_per_node=2))
    cont = pool.create_container("d", oclass="S2")
    dfs = DFS(cont)
    return pool, dfs


def test_corpus_roundtrip(world):
    pool, dfs = world
    corpus = synthetic_corpus(10_000, 256, seed=1)
    n = write_corpus(dfs, corpus, shard_tokens=1024)
    assert n == 10
    ds = ObjectStoreDataset(dfs)
    got = np.concatenate([ds.read_shard(i) for i in range(len(ds))])
    np.testing.assert_array_equal(got, corpus)


def test_prefetcher_order_and_batches(world):
    pool, dfs = world
    corpus = synthetic_corpus(20_000, 256, seed=2)
    write_corpus(dfs, corpus, shard_tokens=2048)
    ds = ObjectStoreDataset(dfs)
    pf = Prefetcher(ds, depth=3)
    batches = list(pf.batches(batch=4, seq=128))
    assert len(batches) >= 30
    assert batches[0]["tokens"].shape == (4, 128)
    # tokens come from the corpus in order
    np.testing.assert_array_equal(batches[0]["tokens"].reshape(-1),
                                  corpus[: 4 * 128])


def test_prefetcher_tolerates_lost_shards(world):
    pool, dfs = world
    corpus = synthetic_corpus(20_000, 256, seed=3)
    write_corpus(dfs, corpus, shard_tokens=2048)  # S2: unprotected
    ds = ObjectStoreDataset(dfs)
    pool.fail_engine(0)
    pool.fail_engine(1)
    pf = Prefetcher(ds, depth=2)
    got = 0
    while True:
        try:
            pf.get()
            got += 1
        except StopIteration:
            break
    assert got + len(pf.failed) == len(ds)
    assert got > 0  # pipeline made progress despite dead engines


def test_failure_detector_and_replan(world):
    pool, _ = world
    det = FailureDetector(pool, n_workers=8)
    assert det.poll(0) == []
    pool.fail_engine(3)
    det.fail_worker(7, step=5)
    events = det.poll(5)
    kinds = {(e.kind, e.ident) for e in events}
    assert ("engine", 3) in kinds and ("worker", 7) in kinds
    assert det.n_alive_workers == 7
    dp, per = replan_data_parallel(256, det.n_alive_workers)
    assert dp <= 7 and 256 % dp == 0 and dp * per == 256
    assert replan_data_parallel(256, 8) == (8, 32)


def test_hlo_cost_scan_multiplier():
    import jax
    import jax.numpy as jnp
    from repro.launch.hlo_cost import analyze

    def f(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=8)
        return y

    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    c = jax.jit(f).lower(x, w).compile()
    r = analyze(c.as_text())
    assert r["flops"] == 8 * 2 * 128 * 256 * 256
    assert r["hbm_bytes"] > 0
    # unscaled XLA report counts the body once: must be 8x smaller
    cost = c.cost_analysis()
    assert float(cost["flops"]) * 8 == r["flops"]
