"""granite-session-return at a tiny size on the CPU (sound, planted faults,
float8 control), the ``hybrid_ssd`` family's counts and weight rules, the
reference's weight table, and the store's ``kv.leaf`` spans as the
recurrent-leaf readers read them."""
from __future__ import annotations

import time
import types

import numpy as np
import pytest

from tiny import BENCH, run_tiny, tiny_cell

from lib import bench as B
from lib import counts, leaves, weights
from lib import program as P
from lib.common import Recorder, jax_key

CELL = "granite-session-return"
GRANITE = BENCH / "configs" / "granite-4.0-h-micro.json"
RECURRENT = ["kv_offload_recurrent_ms", "kv_restore_recurrent_ms"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_granite_cell_runs_and_is_correct(dtype):
    result, checks = run_tiny(CELL, param_dtype=dtype)
    assert result["correct"] is True
    assert result["attempted"] == 8 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "return_ttft_p50_ms",
                                      "tpot_p95_ms"}
    assert [c.name for c in checks] == ["answer_gap_max"]
    if dtype == "float32":
        assert checks[0].value < 1e-6


def _altered_decode(monkeypatch):
    """The program's decode step with its answer token altered."""
    import repro.serve as serve
    real = serve.make_decode_step

    def make(cfg, greedy=True):
        step = real(cfg, greedy)

        def broken(params, cache, tokens, pos):
            tok, logits, cache = step(params, cache, tokens, pos)
            return (tok + 1) % cfg.vocab_size, logits, cache
        return broken
    monkeypatch.setattr(serve, "make_decode_step", make)


def _stale_decode(monkeypatch):
    """The program's decode step returning the cache it was given."""
    import repro.serve as serve
    real = serve.make_decode_step

    def make(cfg, greedy=True):
        step = real(cfg, greedy)

        def broken(params, cache, tokens, pos):
            tok, logits, _ = step(params, cache, tokens, pos)
            return tok, logits, cache
        return broken
    monkeypatch.setattr(serve, "make_decode_step", make)


def _zeroed_state(monkeypatch):
    """A restore whose SSD ``state`` leaf comes back zeroed."""
    import repro.serve as serve
    real = serve.KVCacheStore.restore

    def restore(self, session, *args, **kw):
        tree = real(self, session, *args, **kw)
        return {**tree, "state": np.zeros_like(tree["state"])}
    monkeypatch.setattr(serve.KVCacheStore, "restore", restore)


@pytest.mark.parametrize("fault", [_altered_decode, _stale_decode,
                                   _zeroed_state],
                         ids=["answer-altered", "state-unchanged",
                              "restored-state-zeroed"])
def test_granite_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    result, checks = run_tiny(CELL)
    assert result["correct"] is False
    assert checks[0].value > checks[0].limit


def test_float8_control_reads_wider_than_the_program():
    """The control (the reference in float8, in the program's place) is
    held to the limit by the same checks as the program and comes out not
    correct where the bfloat16 program is correct."""
    import jax

    import run as R
    cell, cfg = tiny_cell(CELL, "bfloat16")
    out = {}
    result, checks = R.run_cell(cell, 7, 0.2, False, jax.devices()[:1],
                                time.perf_counter(), cfg=cfg, out=out)
    assert result["correct"] is True
    control, = out["control"]
    assert control.limit == checks[0].limit
    assert not control.ok
    assert control.value > 4 * checks[0].value


def test_reference_weight_table_is_the_programs():
    import jax

    import repro.models as models
    config = B.read_json(GRANITE)
    ref = B.load_module(BENCH / "refs" / f"{config['reference']}.py")
    cfg = B.program_config(config)
    spec = jax.eval_shape(lambda: models.init_model(jax.random.PRNGKey(0),
                                                    cfg))
    config["padded_vocab_size"] = cfg.padded_vocab()
    assert tuple(ref.layout(config)) == weights.layout_of(spec)
    cell, cfg = tiny_cell(CELL)
    spec = jax.eval_shape(lambda: models.init_model(jax.random.PRNGKey(0),
                                                    cfg))
    assert tuple(ref.layout(cell.config)) == weights.layout_of(spec)


# the published sizes, written out: d 2048, MLP 8192, vocabulary 100352,
# 40 layers of which 4 attention (32 query and 8 KV heads of 64) and 36
# SSD (64 heads of 64, state 128, conv 4 over x, B and C: 4096 + 256)
D, FF, V, HD = 2048, 8192, 100352, 64
SSD_LEAVES = {"w_in": D * (4096 + 4096 + 128 + 128 + 64), "conv": 4 * 4352,
              "conv_bias": 4352, "a_log": 64, "d_skip": 64, "dt_bias": 64,
              "out_norm": 4096, "w_out": 4096 * D}
ATTN_LEAVES = {"wq": D * 32 * HD, "wk": D * 8 * HD, "wv": D * 8 * HD,
               "wo": 32 * HD * D}
BLOCK_LEAVES = {"w_gate": D * FF, "w_up": D * FF, "w_down": FF * D,
                "norm1": D, "norm2": D}
N_PARAMS = (36 * (sum(SSD_LEAVES.values()) + sum(BLOCK_LEAVES.values()))
            + 4 * (sum(ATTN_LEAVES.values()) + sum(BLOCK_LEAVES.values()))
            + V * D + D)
FLOAT32_PARAMS = 36 * 3 * 64
STATE = 36 * 64 * 128 * 64 * 4
CONV = 36 * 3 * 4352 * 2


def kv_bytes(slots):
    return 4 * slots * 8 * HD * 2 * 2


@pytest.mark.parametrize("slots", [4352, 8448, 16640])
def test_hybrid_ssd_counts_match_a_hand_computation(slots):
    c = counts.counts_for(B.read_json(GRANITE))
    matmul = (36 * (SSD_LEAVES["w_in"] + SSD_LEAVES["w_out"])
              + 4 * sum(ATTN_LEAVES.values())
              + 40 * 3 * D * FF + V * D)
    assert (c.n_params, c.matmul_params) == (N_PARAMS, matmul)
    assert c.cache_bytes(slots) == STATE + CONV + kv_bytes(slots)
    # each matrix product 2 ops a weight, attention 2 x 2 ops a query head
    # and slot, the SSD state 3 ops an element to update, 2 to read out
    assert c.decode_flops(slots) == (2 * matmul + 4 * 4 * 32 * HD * slots
                                     + 36 * 64 * 128 * 64 * (3 + 2))
    weights_b = (N_PARAMS - FLOAT32_PARAMS) * 2 + FLOAT32_PARAMS * 4
    assert c.decode_bytes(slots) == (weights_b + D * 2 + 2 * (STATE + CONV)
                                     + kv_bytes(slots) + kv_bytes(1))


def test_hybrid_ssd_n_params_is_the_programs_count():
    import jax

    import repro.models as models
    cfg = B.program_config(B.read_json(GRANITE))
    spec = jax.eval_shape(lambda: models.init_model(jax.random.PRNGKey(0),
                                                    cfg))
    have = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(spec))
    assert have == N_PARAMS == cfg.n_params()


def test_hybrid_ssd_leaf_rules_draw_in_their_ranges():
    layout = (("/ssd_blocks/ssm/a_log", (36, 64), "float32"),
              ("/ssd_blocks/ssm/conv_bias", (36, 4352), "bfloat16"),
              ("/ssd_blocks/ssm/d_skip", (36, 64), "float32"),
              ("/ssd_blocks/ssm/dt_bias", (36, 64), "float32"),
              ("/ssd_blocks/ssm/out_norm", (36, 4096), "bfloat16"),
              ("/embed/tok", (4096, 512), "bfloat16"))
    got = {p: np.asarray(a, np.float32) for p, a in weights.flatten(
        weights.make(jax_key(2 ** 33 + 17, 1), layout, "hybrid_ssd")).items()}
    minus_a = np.exp(got["/ssd_blocks/ssm/a_log"])
    assert 1.0 <= minus_a.min() and minus_a.max() <= 16.0
    assert minus_a.max() - minus_a.min() > 12.0
    dt = np.log1p(np.exp(got["/ssd_blocks/ssm/dt_bias"]))     # softplus
    assert 0.99e-3 <= dt.min() and dt.max() <= 1.01e-1
    assert np.log(dt).std() == pytest.approx(np.log(100) / np.sqrt(12),
                                             rel=0.1)       # log-uniform
    bias = got["/ssd_blocks/ssm/conv_bias"]
    assert -0.5 <= bias.min() and bias.max() <= 0.5 and bias.std() > 0.25
    assert np.all(got["/ssd_blocks/ssm/d_skip"] == 1.0)
    assert np.all(got["/ssd_blocks/ssm/out_norm"] == 1.0)
    assert got["/embed/tok"].std() == pytest.approx(0.02 / 12, rel=0.05)


def _store():
    from repro.core import Pool, Topology
    from repro.core.interfaces import DFS
    from repro.serve import KVCacheStore
    return KVCacheStore(DFS(Pool(Topology()).create_container(
        "serve", oclass="S2")), interface="daos-array", base="/kvcache")


def _hybrid_cache():
    """A hybrid cache: float32 state, bfloat16 conv tail, K/V of which one
    leaf is over the multipart threshold."""
    import ml_dtypes
    rng = np.random.default_rng(3)
    return {"state": rng.standard_normal((6, 1, 8, 16, 16)).astype(
                np.float32),
            "conv": rng.standard_normal((6, 1, 3, 160)).astype(
                ml_dtypes.bfloat16),
            "k": rng.integers(0, 255, (2, 1, 6 << 10, 512), np.uint8),
            "v": rng.standard_normal((2, 1, 48, 2, 16)).astype(np.float32)}


def _round_trip():
    from repro.core import obs
    store, cache = _store(), _hybrid_cache()
    store.offload("s0", cache)          # set-up: outside the window
    rec = Recorder()
    with rec.span("window"):
        with rec.span("restore"):
            store.restore("s0")
        with rec.span("offload"):
            store.offload("s0", cache, step=1)
    (lo, hi), = [(a, b) for n, a, b in rec.spans if n == "window"]
    events = [e for e in obs.events() if lo <= e.start and e.end <= hi]
    return cache, rec, events


def test_one_kv_leaf_span_per_leaf_under_offload_and_restore():
    cache, rec, events = _round_trip()
    spans = [e for e in events if e.kind == "span"]
    for top in ("kv.offload", "kv.restore"):
        t, = [e for e in spans if e.name == top]
        got = {e.attrs["path"]: e.attrs["nbytes"] for e in spans
               if e.name == "kv.leaf" and e.parent == t.id}
        assert got == {f"/{k}": a.nbytes for k, a in cache.items()}
        leaf_ids = {e.id for e in spans if e.name == "kv.leaf"
                    and e.parent == t.id}
        # the leaf's work lies under its span: no fetch, checksum or read
        # directly under the top span
        direct = {e.name for e in spans if e.parent == t.id}
        assert not direct & {"ser.to_host", "integrity.checksum",
                             "obj.array_read", "obj.multipart"}
        assert any(e.parent in leaf_ids for e in spans
                   if e.name == "integrity.checksum")


def test_recurrent_readers_read_the_recurrent_leaves():
    _, rec, events = _round_trip()
    config = B.read_json(GRANITE)
    data = types.SimpleNamespace(rec=rec, cell=types.SimpleNamespace(
        config=config, bench_dir=BENCH))
    got = {m: B.metric_reader(m).read(data) for m in RECURRENT}
    spans = [e for e in events if e.kind == "span"]
    for m, top in zip(RECURRENT, ("kv.offload", "kv.restore")):
        t, = [e for e in spans if e.name == top]
        want = sum(e.end - e.start for e in spans if e.name == "kv.leaf"
                   and e.parent == t.id
                   and e.attrs["path"] in ("/state", "/conv"))
        assert got[m] == pytest.approx(1e3 * want)
        assert 0 < got[m] < 1e3 * (t.end - t.start)
    # a family that lists no recurrent leaf reads nothing
    data.cell.config = B.read_json(BENCH / "configs" /
                                   "h2o-danube-1.8b.json")
    assert [B.metric_reader(m).read(data) for m in RECURRENT] == [None] * 2


def test_kv_leaf_spans_change_no_bucket_of_the_existing_readers():
    """The same recorded events read with the ``kv.leaf`` spans and with
    each one taken out (its children given to the top span): every bucket
    of the existing readers is the same, the leaf spans' own time falling
    into ``untraced``.  Without ``kv.leaf`` spans (a program before them)
    the recurrent readers read nothing."""
    _, _, events = _round_trip()
    leaf = {e.id: e.parent for e in events if e.name == "kv.leaf"}
    without = [e._replace(parent=leaf.get(e.parent, e.parent))
               for e in events if e.name != "kv.leaf"]
    lo = min(e.start for e in events) - 1
    hi = max(e.end for e in events) + 1
    for tops in (("kv.offload",), ("kv.restore",),
                 ("kv.offload", "kv.restore")):
        a = P.breakdown(events, 0, lo, hi, tops)
        b = P.breakdown(without, 0, lo, hi, tops)
        assert set(a) == set(b)
        for k in a:
            assert a[k] == pytest.approx(b[k], rel=1e-9, abs=1e-9), k
    assert leaves.leaf_s(without, 0, lo, hi, "kv.offload",
                         ("state", "conv")) is None
    assert leaves.leaf_s(events, 0, lo, hi, "kv.offload",
                         ("state", "conv"))["n"] == 1
