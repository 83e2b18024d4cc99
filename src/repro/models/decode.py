"""Serving paths: prefill (build cache over a full prompt) and decode
(one token against the cache), per architecture family.

Caches are pytrees with layer-stacked leading dims so the layer loop stays a
`lax.scan`.  Decode attention shardings (KV heads vs sequence over the
'model' axis) are chosen in launch/mesh.py.

SWA architectures allocate ring caches of window length — decoding with a
"32k context" then costs O(window) per step, which is the point of SWA.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import layers as L
from . import moe as M
from . import rglru as R
from . import ssm as S
from . import transformer as T

Params = dict


def cache_len(cfg, seq_len: int) -> int:
    if cfg.swa_window:
        return min(seq_len, cfg.swa_window)
    return seq_len


def cache_spec(cfg, seq_len: int, batch: int, tp_pad: int = 1):
    """ShapeDtypeStruct pytree of the decode cache (for input_specs)."""
    dt = jnp.bfloat16 if cfg.param_dtype == "bfloat16" else jnp.float32
    sds = jax.ShapeDtypeStruct
    Lc = cache_len(cfg, seq_len)
    if cfg.family == "ssm":
        din = cfg.ssm_expand * cfg.d_model
        return {
            "state": sds((cfg.n_layers, batch, cfg.ssm_heads, cfg.ssm_state,
                          cfg.ssm_headdim), jnp.float32),
            "conv": sds((cfg.n_layers, batch, cfg.conv_width - 1,
                         din + 2 * cfg.ssm_state), dt),
        }
    if cfg.family == "hybrid_ssd":
        n_attn = T.block_kinds(cfg).count("attn")
        din = cfg.ssm_expand * cfg.d_model
        kv = sds((n_attn, batch, Lc, cfg.n_kv_heads, cfg.head_dim), dt)
        return {
            "state": sds((cfg.n_layers - n_attn, batch, cfg.ssm_heads,
                          cfg.ssm_state, cfg.ssm_headdim), jnp.float32),
            "conv": sds((cfg.n_layers - n_attn, batch, cfg.conv_width - 1,
                         din + 2 * cfg.ssm_state), dt),
            "k": kv, "v": kv,
        }
    if cfg.family == "hybrid":
        kinds = T.block_kinds(cfg)
        n_attn = sum(1 for k in kinds if k == "local_attn")
        n_rec = len(kinds) - n_attn
        w = cfg.lru_width or cfg.d_model
        Wloc = min(seq_len, cfg.local_window)
        return {
            "rec_h": sds((n_rec, batch, w), jnp.float32),
            "rec_conv": sds((n_rec, batch, cfg.conv_width - 1, w), dt),
            "k": sds((n_attn, batch, Wloc, cfg.n_kv_heads, cfg.head_dim), dt),
            "v": sds((n_attn, batch, Wloc, cfg.n_kv_heads, cfg.head_dim), dt),
        }
    if cfg.family == "encdec":
        Se = seq_len // 2
        Sd = seq_len - Se
        return {
            "k": sds((cfg.dec_layers, batch, Sd, cfg.n_kv_heads,
                      cfg.head_dim), dt),
            "v": sds((cfg.dec_layers, batch, Sd, cfg.n_kv_heads,
                      cfg.head_dim), dt),
            "xk": sds((cfg.dec_layers, batch, Se, cfg.n_kv_heads,
                       cfg.head_dim), dt),
            "xv": sds((cfg.dec_layers, batch, Se, cfg.n_kv_heads,
                       cfg.head_dim), dt),
        }
    return {
        "k": sds((cfg.n_layers, batch, Lc, cfg.n_kv_heads, cfg.head_dim), dt),
        "v": sds((cfg.n_layers, batch, Lc, cfg.n_kv_heads, cfg.head_dim), dt),
    }


def init_cache(cfg, seq_len: int, batch: int):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_spec(cfg, seq_len, batch))


# ======================================================================
# decode: one token
# ======================================================================

def forward_decode(params: Params, cfg, cache: dict, tokens: jnp.ndarray,
                   pos: jnp.ndarray):
    """tokens: (B, 1) int32; pos: scalar int32 (current position).
    Returns (hidden (B, 1, d), cache')."""
    n_heads = T.params_n_heads(params, cfg)
    x = L.embed(params["embed"], tokens, cfg.embed_mult)
    if cfg.rotary_pct == 0.0 and cfg.family != "ssm" and not cfg.nope:
        B = x.shape[0]
        posv = jnp.broadcast_to(pos[None, None], (B, 1))
        x = x + T._sinusoidal(posv, cfg.d_model).astype(x.dtype)

    if cfg.family == "ssm":
        def step(xx, inp):
            lp, st, cv = inp
            h = L.rms_norm(xx, lp["norm1"])
            y, st2, cv2 = S.ssd_decode_step(lp["ssm"], h, cfg, st, cv)
            return xx + y, (st2, cv2)
        x, (st, cv) = jax.lax.scan(step, x, (params["blocks"],
                                             cache["state"], cache["conv"]))
        return x, {"state": st, "conv": cv}

    if cfg.family == "hybrid":
        return _hybrid_decode(params, cfg, cache, x, pos, n_heads)

    if cfg.family == "hybrid_ssd":
        return _hybrid_ssd_decode(params, cfg, cache, x, pos, n_heads)

    if cfg.family == "encdec":
        return _encdec_decode(params, cfg, cache, x, pos, n_heads)

    def step(xx, inp):
        lp, ck, cv = inp
        h = L.rms_norm(xx, lp["norm1"])
        out, ck, cv = L.attention_decode(lp["attn"], h, cfg, ck, cv, pos,
                                         n_heads)
        xx = xx + out
        xx, _ = T._apply_mlp_or_moe(lp, xx, cfg)
        return xx, (ck, cv)

    x, (k2, v2) = jax.lax.scan(step, x, (params["blocks"], cache["k"],
                                         cache["v"]))
    return x, {"k": k2, "v": v2}


def _hybrid_decode(params, cfg, cache, x, pos, n_heads):
    kinds = T.block_kinds(cfg)
    n_attn = sum(1 for k in kinds if k == "local_attn")
    n_super = n_attn
    rec_used = 2 * n_super
    rec_p = params["rec_blocks"]
    super_rec = jax.tree.map(
        lambda a: a[:rec_used].reshape(2, n_super, *a.shape[1:])
        .swapaxes(0, 1), rec_p)
    rh = cache["rec_h"][:rec_used].reshape(2, n_super, *cache["rec_h"].shape[1:]).swapaxes(0, 1)
    rc = cache["rec_conv"][:rec_used].reshape(2, n_super, *cache["rec_conv"].shape[1:]).swapaxes(0, 1)

    def super_step(xx, inp):
        rp, ap, rhh, rcc, ck, cv = inp
        new_h, new_c = [], []
        for i in range(2):
            sub = jax.tree.map(lambda a: a[i], rp)
            h = L.rms_norm(xx, sub["norm1"])
            y, hf, cf = R.rglru_decode_step(sub["rec"], h, cfg,
                                            rhh[i], rcc[i])
            xx = xx + y
            xx, _ = T._apply_mlp_or_moe(sub, xx, cfg)
            new_h.append(hf)
            new_c.append(cf)
        h = L.rms_norm(xx, ap["norm1"])
        out, ck, cv = L.attention_decode(ap["attn"], h, cfg, ck, cv, pos,
                                         n_heads)
        xx = xx + out
        xx, _ = T._apply_mlp_or_moe(ap, xx, cfg)
        return xx, (jnp.stack(new_h), jnp.stack(new_c), ck, cv)

    x, (rh2, rc2, k2, v2) = jax.lax.scan(
        super_step, x, (super_rec, params["attn_blocks"], rh, rc,
                        cache["k"], cache["v"]))

    rh_flat = rh2.swapaxes(0, 1).reshape(rec_used, *rh2.shape[2:])
    rc_flat = rc2.swapaxes(0, 1).reshape(rec_used, *rc2.shape[2:])
    n_left = len(kinds) - 3 * n_super
    if n_left:
        left = jax.tree.map(lambda a: a[rec_used:], rec_p)

        def left_step(xx, inp):
            lp, hh, cc = inp
            h = L.rms_norm(xx, lp["norm1"])
            y, hf, cf = R.rglru_decode_step(lp["rec"], h, cfg, hh, cc)
            xx = xx + y
            xx, _ = T._apply_mlp_or_moe(lp, xx, cfg)
            return xx, (hf, cf)
        x, (lh, lc) = jax.lax.scan(
            left_step, x, (left, cache["rec_h"][rec_used:],
                           cache["rec_conv"][rec_used:]))
        rh_flat = jnp.concatenate([rh_flat, lh])
        rc_flat = jnp.concatenate([rc_flat, lc])
    return x, {"rec_h": rh_flat, "rec_conv": rc_flat, "k": k2, "v": v2}


def _hybrid_ssd_decode(params, cfg, cache, x, pos, n_heads):
    def ssd_fn(xx, p, c):
        h = L.rms_norm(xx, p["norm1"])
        y, state, conv = S.ssd_decode_step(p["ssm"], h, cfg, *c)
        xx, _ = T._apply_mlp_or_moe(p, T._residual(xx, y, cfg), cfg)
        return xx, (state, conv)

    def attn_fn(xx, p, c):
        h = L.rms_norm(xx, p["norm1"])
        out, k, v = L.attention_decode(p["attn"], h, cfg, *c, pos, n_heads)
        xx, _ = T._apply_mlp_or_moe(p, T._residual(xx, out, cfg), cfg)
        return xx, (k, v)

    x, ((state, conv), (k, v)) = T.hybrid_ssd_stack(
        params, cfg, x, ssd_fn, attn_fn, rec=(cache["state"], cache["conv"]),
        kv=(cache["k"], cache["v"]))
    return x, {"state": state, "conv": conv, "k": k, "v": v}


def _encdec_decode(params, cfg, cache, x, pos, n_heads):
    def step(xx, inp):
        lp, ck, cv, xk, xv = inp
        h = L.rms_norm(xx, lp["norm1"])
        out, ck, cv = L.attention_decode(lp["attn"], h, cfg, ck, cv, pos,
                                         n_heads)
        xx = xx + out
        # cross attention against the precomputed encoder cache
        h = L.rms_norm(xx, lp["norm3"])
        q = L._split_heads(h @ lp["xattn"]["wq"], n_heads, cfg.head_dim)
        out = L.gqa_scores_softmax_v(q, xk.astype(q.dtype),
                                     xv.astype(q.dtype), None,
                                     cfg.n_kv_heads)
        xx = xx + out.reshape(*xx.shape[:2], -1) @ lp["xattn"]["wo"]
        xx, _ = T._apply_mlp_or_moe(lp, xx, cfg)
        return xx, (ck, cv)

    x, (k2, v2) = jax.lax.scan(
        step, x, (params["decoder"], cache["k"], cache["v"], cache["xk"],
                  cache["xv"]))
    return x, {"k": k2, "v": v2, "xk": cache["xk"], "xv": cache["xv"]}


# ======================================================================
# prefill: full prompt -> cache
# ======================================================================

def _fit_cache_seq(k: jnp.ndarray, target: int) -> jnp.ndarray:
    """k: (L, B, S', H, D). Keep the last `target` positions / zero-pad up
    to `target` slots (slot i == position i, so decode's ring write at
    pos >= S' lands in the padded region)."""
    S_ = k.shape[2]
    if target == S_:
        return k
    if target < S_:
        return k[:, :, -target:]
    pad = jnp.zeros(k.shape[:2] + (target - S_,) + k.shape[3:], k.dtype)
    return jnp.concatenate([k, pad], axis=2)


def forward_prefill(params: Params, cfg, batch, pad_to: int | None = None):
    """-> (hidden (B, S, d), cache). Builds the serving cache; `pad_to`
    sizes the KV cache for subsequent decode steps (defaults to the
    prompt length + 1)."""
    n_heads = T.params_n_heads(params, cfg)
    if cfg.family == "encdec":
        return _encdec_prefill(params, cfg, batch, n_heads, pad_to)
    x, positions = T._embed_inputs(params, cfg, batch)
    window = cfg.swa_window
    prefix = cfg.n_prefix_tokens if cfg.family == "vlm" else 0
    pad_to = pad_to if pad_to is not None else x.shape[1] + 1

    if cfg.family == "ssm":
        def step(xx, lp):
            xx, (st, cv) = T._ssm_block(lp, xx, cfg)
            return xx, (st, cv)
        x, (st, cv) = jax.lax.scan(step, x, params["blocks"])
        return x, {"state": st, "conv": cv}

    if cfg.family == "hybrid":
        return _hybrid_prefill(params, cfg, x, positions, n_heads, pad_to)

    if cfg.family == "hybrid_ssd":
        return T.hybrid_ssd_full(params, cfg, x, positions, n_heads,
                                 pad_to=pad_to)

    Lc = cache_len(cfg, max(x.shape[1], pad_to))

    def step(xx, lp):
        xx, aux, kv = T._dense_block(lp, xx, cfg, positions,
                                     n_heads=n_heads, window=window,
                                     prefix=prefix, collect_kv=True)
        k, v = kv
        return xx, (_fit_cache_seq(k[None], Lc)[0],
                    _fit_cache_seq(v[None], Lc)[0])

    x, (k, v) = jax.lax.scan(step, x, params["blocks"])
    return x, {"k": k, "v": v}


def _hybrid_prefill(params, cfg, x, positions, n_heads, pad_to=None):
    kinds = T.block_kinds(cfg)
    n_attn = sum(1 for k in kinds if k == "local_attn")
    n_super = n_attn
    rec_used = 2 * n_super
    rec_p = params["rec_blocks"]
    super_rec = jax.tree.map(
        lambda a: a[:rec_used].reshape(2, n_super, *a.shape[1:])
        .swapaxes(0, 1), rec_p)
    pad_to = pad_to if pad_to is not None else x.shape[1] + 1
    Wloc = min(max(x.shape[1], pad_to), cfg.local_window)

    def super_step(xx, inp):
        rp, ap = inp
        hs, cs = [], []
        for i in range(2):
            sub = jax.tree.map(lambda a: a[i], rp)
            xx, hf, cf = T._rec_block(sub, xx, cfg)
            hs.append(hf)
            cs.append(cf)
        xx, _, kv = T._dense_block(ap, xx, cfg, positions, n_heads=n_heads,
                                   window=cfg.local_window, prefix=0,
                                   collect_kv=True)
        k, v = kv
        return xx, (jnp.stack(hs), jnp.stack(cs),
                    _fit_cache_seq(k[None], Wloc)[0],
                    _fit_cache_seq(v[None], Wloc)[0])

    x, (rh, rc, k, v) = jax.lax.scan(super_step, x,
                                     (super_rec, params["attn_blocks"]))
    rh_flat = rh.swapaxes(0, 1).reshape(rec_used, *rh.shape[2:])
    rc_flat = rc.swapaxes(0, 1).reshape(rec_used, *rc.shape[2:])
    n_left = len(kinds) - 3 * n_super
    if n_left:
        left = jax.tree.map(lambda a: a[rec_used:], rec_p)

        def left_step(xx, lp):
            xx, hf, cf = T._rec_block(lp, xx, cfg)
            return xx, (hf, cf)
        x, (lh, lc) = jax.lax.scan(left_step, x, left)
        rh_flat = jnp.concatenate([rh_flat, lh])
        rc_flat = jnp.concatenate([rc_flat, lc])
    return x, {"rec_h": rh_flat, "rec_conv": rc_flat, "k": k, "v": v}


def _encdec_prefill(params, cfg, batch, n_heads, pad_to=None):
    enc_x = batch["src_emb"].astype(L._dtype(cfg))
    B, Se, d = enc_x.shape
    enc_pos = jnp.broadcast_to(jnp.arange(Se)[None], (B, Se))
    enc_x = enc_x + T._sinusoidal(enc_pos, d).astype(enc_x.dtype)

    def enc_fn(xx, lp):
        xx, _ = T._apply_attn_block(lp, xx, cfg, enc_pos, n_heads=n_heads,
                                    causal=False)
        xx, aux = T._apply_mlp_or_moe(lp, xx, cfg)
        return xx, None
    enc_out, _ = jax.lax.scan(enc_fn, enc_x, params["encoder"])

    dec_x, dec_pos = T._embed_inputs(params, cfg, {"tokens": batch["tokens"]})

    def dec_fn(xx, lp):
        xx, kv = T._apply_attn_block(lp, xx, cfg, dec_pos, n_heads=n_heads,
                                     causal=True)
        xp = {"attn": lp["xattn"], "norm1": lp["norm3"]}
        xx, xkv = T._apply_attn_block(xp, xx, cfg, dec_pos, n_heads=n_heads,
                                      causal=False, kv_override=enc_out)
        xx, _ = T._apply_mlp_or_moe(lp, xx, cfg)
        return xx, (kv[0], kv[1], xkv[0], xkv[1])

    dec_out, (k, v, xk, xv) = jax.lax.scan(dec_fn, dec_x, params["decoder"])
    pad_to = pad_to if pad_to is not None else dec_x.shape[1] + 1
    return dec_out, {"k": _fit_cache_seq(k, pad_to),
                     "v": _fit_cache_seq(v, pad_to), "xk": xk, "xv": xv}
