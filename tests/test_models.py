"""Model math correctness: each optimized path against a naive reference."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, smoke_variant
from repro.configs.base import ShapeConfig
from repro.models import init_model, make_inputs
from repro.models.attention_flash import blockwise_attention
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.models.model import forward_prefill, forward_train, forward_decode

rng = np.random.default_rng(7)


def naive_attention(q, k, v, n_kv, causal=True, window=0, prefix=0):
    B, Sq, Hq, D = q.shape
    mask = L.causal_mask(Sq, window=window, prefix=prefix) if causal else None
    if not causal:
        mask = jnp.zeros((Sq, k.shape[1]))
    return L.gqa_scores_softmax_v(q, k, v, mask, n_kv)


@pytest.mark.parametrize("S_,Hq,n_kv,window,prefix", [
    (64, 4, 2, 0, 0),       # causal GQA
    (64, 4, 1, 0, 0),       # MQA
    (96, 4, 4, 32, 0),      # sliding window
    (64, 4, 2, 0, 16),      # prefix-LM
])
def test_flash_matches_naive(S_, Hq, n_kv, window, prefix):
    B, D = 2, 16
    q = jnp.asarray(rng.normal(size=(B, S_, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S_, n_kv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S_, n_kv, D)), jnp.float32)
    out_flash = blockwise_attention(q, k, v, n_kv, causal=True,
                                    window=window, prefix=prefix,
                                    bq=16, bk=32)
    out_naive = naive_attention(q, k, v, n_kv, window=window, prefix=prefix)
    np.testing.assert_allclose(np.asarray(out_flash), np.asarray(out_naive),
                               rtol=2e-4, atol=2e-4)


def test_flash_bidirectional_matches():
    B, S_, H, D = 2, 48, 4, 16
    q = jnp.asarray(rng.normal(size=(B, S_, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S_, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S_, H, D)), jnp.float32)
    out = blockwise_attention(q, k, v, H, causal=False, bq=16, bk=16)
    ref = naive_attention(q, k, v, H, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def _moe_cfg(k=2, E=8):
    base = smoke_variant(ARCHS["qwen3-moe-235b-a22b"])
    return dataclasses.replace(base, n_experts=E, experts_per_token=k,
                               capacity_factor=8.0)  # no drops


def test_moe_matches_dense_mixture():
    """With generous capacity, gathered MoE == explicit per-token mixture."""
    cfg = _moe_cfg()
    key = jax.random.PRNGKey(0)
    p = M.init_moe(key, cfg)
    x = jnp.asarray(rng.normal(size=(2, 16, cfg.d_model)) * 0.3, jnp.float32)
    y, aux = M.moe_ffn(p, x, cfg, n_groups=1)

    # dense reference: every expert on every token, weighted by router
    xf = x.reshape(-1, cfg.d_model)
    logits = xf @ p["router"]
    probs = jax.nn.softmax(logits, -1)
    topv, topi = jax.lax.top_k(probs, cfg.experts_per_token)
    topv = topv / topv.sum(-1, keepdims=True)
    h = jnp.einsum("td,edf->tef", xf, p["w_gate"])
    h = jax.nn.silu(h) * jnp.einsum("td,edf->tef", xf, p["w_up"])
    all_out = jnp.einsum("tef,efd->ted", h, p["w_down"])
    ref = jnp.zeros_like(xf)
    for c in range(cfg.experts_per_token):
        ref = ref + jnp.take_along_axis(
            all_out, topi[:, c][:, None, None], axis=1)[:, 0] \
            * topv[:, c][:, None]
    np.testing.assert_allclose(np.asarray(y.reshape(-1, cfg.d_model)),
                               np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_moe_group_invariance():
    """Group count must not change results (groups are a sharding detail)."""
    cfg = _moe_cfg()
    p = M.init_moe(jax.random.PRNGKey(1), cfg)
    x = jnp.asarray(rng.normal(size=(4, 8, cfg.d_model)) * 0.3, jnp.float32)
    y1, _ = M.moe_ffn(p, x, cfg, n_groups=1)
    y2, _ = M.moe_ffn(p, x, cfg, n_groups=4)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-3, atol=2e-3)


def test_ssd_chunked_matches_sequential():
    """Chunked SSD == step-by-step recurrence."""
    cfg = smoke_variant(ARCHS["mamba2-370m"])
    cfg = dataclasses.replace(cfg, ssm_chunk=8)
    p = S.init_ssm(jax.random.PRNGKey(0), cfg)
    B, S_ = 2, 32
    x = jnp.asarray(rng.normal(size=(B, S_, cfg.d_model)) * 0.3, jnp.float32)
    y_chunked, final, _tail = S.ssd_forward(p, x, cfg)

    state = jnp.zeros((B, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_headdim),
                      jnp.float32)
    conv = jnp.zeros((B, cfg.conv_width - 1,
                      cfg.ssm_expand * cfg.d_model + 2 * cfg.ssm_state),
                     jnp.float32)
    ys = []
    for t in range(S_):
        y_t, state, conv = S.ssd_decode_step(p, x[:, t:t + 1], cfg, state,
                                             conv)
        ys.append(y_t)
    y_seq = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_chunked), np.asarray(y_seq),
                               rtol=3e-3, atol=3e-3)
    np.testing.assert_allclose(np.asarray(final), np.asarray(state),
                               rtol=3e-3, atol=3e-3)


def test_ssd_with_conv_bias_and_initial_state_matches_sequential():
    """Chunked SSD with a conv bias, started from a given state, ==
    step-by-step decode from that state (the conv starting from zeros)."""
    cfg = dataclasses.replace(smoke_variant(ARCHS["granite-4.0-h-micro"]),
                              ssm_chunk=8)
    assert cfg.conv_bias
    p = S.init_ssm(jax.random.PRNGKey(1), cfg)
    p["conv_bias"] = jnp.asarray(rng.uniform(-0.5, 0.5,
                                             p["conv_bias"].shape),
                                 jnp.float32)
    B, S_ = 2, 32
    x = jnp.asarray(rng.normal(size=(B, S_, cfg.d_model)) * 0.3, jnp.float32)
    state0 = jnp.asarray(rng.normal(size=(B, cfg.ssm_heads, cfg.ssm_state,
                                          cfg.ssm_headdim)), jnp.float32)
    y_chunked, final, tail = S.ssd_forward(p, x, cfg, initial_state=state0)

    state = state0
    conv = jnp.zeros((B, cfg.conv_width - 1,
                      cfg.ssm_expand * cfg.d_model + 2 * cfg.ssm_state),
                     jnp.float32)
    ys = []
    for t in range(S_):
        y_t, state, conv = S.ssd_decode_step(p, x[:, t:t + 1], cfg, state,
                                             conv)
        ys.append(y_t)
    y_seq = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_chunked), np.asarray(y_seq),
                               rtol=3e-3, atol=3e-3)
    np.testing.assert_allclose(np.asarray(final), np.asarray(state),
                               rtol=3e-3, atol=3e-3)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(conv),
                               rtol=1e-5, atol=1e-5)
    # the bias and the initial state both reach the output
    p0 = dict(p, conv_bias=jnp.zeros_like(p["conv_bias"]))
    assert not np.allclose(np.asarray(S.ssd_forward(p0, x, cfg,
                                                    initial_state=state0)[0]),
                           np.asarray(y_chunked), atol=1e-3)
    assert not np.allclose(np.asarray(S.ssd_forward(p, x, cfg)[0]),
                           np.asarray(y_chunked), atol=1e-3)


def test_hybrid_ssd_smoke_variant_keeps_both_kinds_of_layer():
    """The smoke variant of granite keeps SSD and attention layers in a
    whole number of periods, and the cache holds both kinds of state."""
    from repro.models import cache_spec
    from repro.models.transformer import block_kinds
    full = ARCHS["granite-4.0-h-micro"]
    kinds = block_kinds(full)
    assert [i for i, k in enumerate(kinds) if k == "attn"] == [5, 15, 25, 35]
    cfg = smoke_variant(full)
    kinds = block_kinds(cfg)
    assert cfg.family == "hybrid_ssd" and cfg.n_layers % cfg.attn_period == 0
    assert kinds.count("attn") >= 2 and kinds.count("ssd") >= 2
    assert cfg.n_kv_heads < cfg.n_heads          # still GQA
    spec = cache_spec(cfg, 16, 1)
    assert set(spec) == {"state", "conv", "k", "v"}
    assert spec["state"].shape[0] == kinds.count("ssd")
    assert spec["k"].shape[:3] == (kinds.count("attn"), 1, 16)


# h2o-danube-1.8b at smoke size, PRNGKey(11) weights, 12 tokens from
# PRNGKey(12), prefill into 16 slots, then one decode step: the first six
# logits of each, as the code before the hybrid_ssd family computed them
DANUBE_PREFILL = [-0.4174935221672058, 0.48753470182418823,
                  -1.1035945415496826, 0.8552388548851013,
                  -0.44491860270500183, -0.19133174419403076]
DANUBE_DECODE = [-0.8570241332054138, 0.6546972990036011,
                 -0.4108631908893585, 1.3991092443466187,
                 -1.0463312864303589, 0.578430712223053]


def test_danube_prefill_and_decode_logits_are_pinned():
    from repro.serve import make_decode_step, make_prefill_step
    cfg = smoke_variant(ARCHS["h2o-danube-1.8b"])
    params = init_model(jax.random.PRNGKey(11), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(12), (1, 12), 0,
                              cfg.vocab_size)
    lg, cache = make_prefill_step(cfg, pad_to=16)(params, {"tokens": toks})
    tok, lg2, _ = make_decode_step(cfg)(params, cache, toks[:, -1:],
                                        jnp.int32(12))
    np.testing.assert_allclose(np.asarray(lg[0, -1, :6]), DANUBE_PREFILL,
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(lg2[0, -1, :6]), DANUBE_DECODE,
                               rtol=1e-6, atol=1e-6)
    assert int(tok[0, 0]) == 87


@pytest.mark.parametrize("arch", ["deepseek-7b", "h2o-danube-1.8b",
                                  "paligemma-3b", "recurrentgemma-9b",
                                  "mamba2-370m", "qwen3-moe-235b-a22b",
                                  "seamless-m4t-large-v2",
                                  "granite-4.0-h-micro"])
def test_prefill_then_decode_matches_full_forward(arch):
    """logits(prefill S tokens, decode token S) == logits(forward S+1)."""
    cfg = smoke_variant(ARCHS[arch])
    if cfg.family == "moe":
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    key = jax.random.PRNGKey(3)
    params = init_model(key, cfg)
    Sfull = 24
    shape_full = ShapeConfig("t", Sfull, 2, "train")
    batch = make_inputs(key, cfg, shape_full)

    hidden_full, _ = forward_train(params, cfg, batch)

    # prefill on all but the last token, then decode it
    St = batch["tokens"].shape[1]
    batch_prefill = dict(batch)
    batch_prefill["tokens"] = batch["tokens"][:, :-1]
    if cfg.family == "encdec":
        pass  # src_emb unchanged
    hidden_pf, cache = forward_prefill(params, cfg, batch_prefill)
    pos = jnp.asarray(
        (St - 1) + (cfg.n_prefix_tokens if cfg.family == "vlm" else 0),
        jnp.int32)
    hidden_dec, _ = forward_decode(params, cfg, cache,
                                   batch["tokens"][:, -1:], pos)
    np.testing.assert_allclose(np.asarray(hidden_dec[:, 0]),
                               np.asarray(hidden_full[:, -1]),
                               rtol=0.05, atol=0.05)
