"""Shared model layers: norms, rotary embeddings, attention (GQA/MQA, causal
/ sliding-window / prefix-LM masks, KV caches), MLPs.

Everything is pure-functional: params are plain dicts of arrays; init_*
builds them, apply functions consume them.  Compute runs in bfloat16 with
fp32 softmax/norm accumulations; weights carry the config's param_dtype.
TP conventions (who shards what) live in launch/mesh.py, not here — layers
only define math, so the same code lowers on 1 CPU device and on the
(pod, data, model) production mesh.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

Params = dict

# --------------------------- activation sharding ---------------------------
# GSPMD left alone will happily propagate *weight* shardings into
# activations (replicating the batch!).  The launcher pins the batch axis
# here; shard_batch() is applied after embedding and at block boundaries.
_BATCH_AXES = None  # e.g. ('data',) or ('pod', 'data'); None = no constraint
_TP_AXIS = None     # 'model' on the production mesh


def set_activation_sharding(batch_axes, tp_axis=None) -> None:
    global _BATCH_AXES, _TP_AXIS
    _BATCH_AXES = batch_axes
    _TP_AXIS = tp_axis


def shard_batch(x: jnp.ndarray) -> jnp.ndarray:
    """Constrain dim 0 to the data-parallel axes (no-op outside a mesh)."""
    if _BATCH_AXES is None:
        return x
    spec = P(_BATCH_AXES, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, spec)


def shard_expert(x: jnp.ndarray, expert_dim: int = 1,
                 n_experts: int = 0) -> jnp.ndarray:
    """Constrain dim 0 to DP and `expert_dim` to the TP axis (MoE buffers)."""
    if _BATCH_AXES is None:
        return x
    spec = [None] * x.ndim
    spec[0] = _BATCH_AXES
    if _TP_AXIS is not None:
        spec[expert_dim] = _TP_AXIS
    return jax.lax.with_sharding_constraint(x, P(*spec))


def _dtype(cfg):
    return jnp.bfloat16 if cfg.param_dtype == "bfloat16" else jnp.float32


def _init(key, shape, scale=None, dtype=jnp.float32):
    scale = scale if scale is not None else 1.0 / np.sqrt(shape[0])
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# --------------------------- norms ---------------------------

_NORM_BF16 = False  # hillclimb H5: bf16 norm products (fp32 variance only)


def set_norm_bf16(flag: bool) -> None:
    global _NORM_BF16
    _NORM_BF16 = flag


@jax.custom_vjp
def _rms_norm_bf16(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    y, _ = _rms_fwd(x, w)
    return y


def _rms_fwd(x, w):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    inv = jax.lax.rsqrt(var + 1e-6).astype(x.dtype)
    return x * inv * w.astype(x.dtype), (x, w, inv)


def _rms_bwd(res, dy):
    # All full-size products in the residual dtype; only (B,S,1)/(d,)
    # reductions accumulate fp32 — no activation-sized fp32 buffers in the
    # backward (hillclimb H7, EXPERIMENTS.md §Perf).
    x, w, inv = res
    xhat = x * inv
    dxhat = dy * w.astype(dy.dtype)
    dw = jnp.einsum("...d,...d->d", dy.astype(jnp.float32),
                    xhat.astype(jnp.float32)).astype(w.dtype)
    mean_term = (jnp.einsum("...sd,...sd->...s", dxhat.astype(jnp.float32),
                            xhat.astype(jnp.float32))
                 / x.shape[-1]).astype(x.dtype)[..., None]
    dx = (dxhat - xhat * mean_term) * inv
    return dx.astype(x.dtype), dw


_rms_norm_bf16.defvjp(_rms_fwd, _rms_bwd)


def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    if _NORM_BF16:
        # measured-best variant (H5/H6; the custom-VJP H7 above was
        # refuted — see EXPERIMENTS.md §Perf): bf16 products, fp32 reduce.
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True,
                       dtype=jnp.float32)
        inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
        return x * inv * w.astype(x.dtype)
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)
            * w.astype(jnp.float32)).astype(x.dtype)


# --------------------------- rotary ---------------------------

def rope_freqs(head_dim: int, pct: float, theta: float):
    rot = int(head_dim * pct) // 2 * 2
    if rot == 0:
        return None
    inv = 1.0 / (theta ** (np.arange(0, rot, 2, np.float32) / rot))
    return jnp.asarray(inv)  # (rot/2,)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, pct: float,
               theta: float) -> jnp.ndarray:
    """x: (..., S, H, D); positions: (..., S) int32. Rotates the first
    pct*D dims pairwise (half-split convention)."""
    D = x.shape[-1]
    inv = rope_freqs(D, pct, theta)
    if inv is None:
        return x
    rot = inv.shape[0] * 2
    ang = positions[..., :, None].astype(jnp.float32) * inv  # (..., S, rot/2)
    cos = jnp.cos(ang)[..., :, None, :]   # (..., S, 1, rot/2)
    sin = jnp.sin(ang)[..., :, None, :]
    if _NORM_BF16:
        # H5: angles in fp32, rotation products in the residual dtype — the
        # (B,S,H,D)-sized fp32 chains (and their backward) disappear.
        cos = cos.astype(x.dtype)
        sin = sin.astype(x.dtype)
        xr, xp = x[..., :rot], x[..., rot:]
        x1, x2 = xr[..., : rot // 2], xr[..., rot // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x2 * cos + x1 * sin, xp], axis=-1)
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., : rot // 2], xr[..., rot // 2:]
    y1 = (x1.astype(jnp.float32) * cos - x2.astype(jnp.float32) * sin)
    y2 = (x2.astype(jnp.float32) * cos + x1.astype(jnp.float32) * sin)
    return jnp.concatenate([y1.astype(x.dtype), y2.astype(x.dtype), xp],
                           axis=-1)


# --------------------------- masks ---------------------------

def causal_mask(S: int, window: int = 0, prefix: int = 0,
                dtype=jnp.float32) -> jnp.ndarray:
    """(S, S) additive mask. window>0 => sliding window; prefix>0 => first
    `prefix` positions attend bidirectionally (prefix-LM)."""
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    allow = j <= i
    if window:
        allow &= (i - j) < window
    if prefix:
        allow |= (j < prefix)  # prefix block is bidirectional & fully visible
    return jnp.where(allow, 0.0, -1e30).astype(dtype)


# --------------------------- attention ---------------------------

def init_attention(key, cfg, tp_pad: int = 1) -> Params:
    d = cfg.d_model
    hq = cfg.padded_heads(tp_pad)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    dt = _dtype(cfg)
    wq = _init(k1, (d, hq * cfg.head_dim), dtype=dt)
    if hq != cfg.n_heads:  # zero the pad heads: exact math
        wq = wq.at[:, cfg.n_heads * cfg.head_dim:].set(0)
    wo = _init(k4, (hq * cfg.head_dim, d), dtype=dt)
    if hq != cfg.n_heads:
        wo = wo.at[cfg.n_heads * cfg.head_dim:, :].set(0)
    return {
        "wq": wq,
        "wk": _init(k2, (d, cfg.kv_dim), dtype=dt),
        "wv": _init(k3, (d, cfg.kv_dim), dtype=dt),
        "wo": wo,
    }


def _split_heads(x, n_heads, head_dim):
    return x.reshape(*x.shape[:-1], n_heads, head_dim)


def gqa_scores_softmax_v(q, k, v, mask, n_kv, scale=None):
    """q: (B,Sq,Hq,D), k/v: (B,Sk,Hkv,D). Returns (B,Sq,Hq,D).
    Hq % Hkv == 0; groups broadcast.  Scores are scaled by ``scale``,
    else by 1/sqrt(D)."""
    B, Sq, Hq, D = q.shape
    G = Hq // n_kv
    qg = q.reshape(B, Sq, n_kv, G, D)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores / np.sqrt(D) if scale is None else scores * scale
    if mask is not None:
        scores = scores + mask
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(B, Sq, Hq, D)


def attention_full(params: Params, x: jnp.ndarray, cfg,
                   positions: jnp.ndarray, mask: jnp.ndarray,
                   n_heads: int) -> jnp.ndarray:
    """Full-sequence attention (train / prefill)."""
    B, S, d = x.shape
    q = _split_heads(x @ params["wq"], n_heads, cfg.head_dim)
    k = _split_heads(x @ params["wk"], cfg.n_kv_heads, cfg.head_dim)
    v = _split_heads(x @ params["wv"], cfg.n_kv_heads, cfg.head_dim)
    q = apply_rope(q, positions, cfg.rotary_pct, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rotary_pct, cfg.rope_theta)
    out = gqa_scores_softmax_v(q, k, v, mask, cfg.n_kv_heads)
    return out.reshape(B, S, -1) @ params["wo"]


def attention_decode(params: Params, x: jnp.ndarray, cfg,
                     cache_k: jnp.ndarray, cache_v: jnp.ndarray,
                     pos: jnp.ndarray, n_heads: int):
    """One-token decode against a (B, S_cache, Hkv, D) cache.
    pos: scalar int32 — current position (same for all rows).
    Returns (out (B,1,d), new_k, new_v)."""
    B, one, d = x.shape
    S_cache = cache_k.shape[1]
    q = _split_heads(x @ params["wq"], n_heads, cfg.head_dim)
    k = _split_heads(x @ params["wk"], cfg.n_kv_heads, cfg.head_dim)
    v = _split_heads(x @ params["wv"], cfg.n_kv_heads, cfg.head_dim)
    posv = jnp.full((B, 1), pos, jnp.int32)
    q = apply_rope(q, posv, cfg.rotary_pct, cfg.rope_theta)
    k = apply_rope(k, posv, cfg.rotary_pct, cfg.rope_theta)
    cache_k = jax.lax.dynamic_update_slice(
        cache_k, k.astype(cache_k.dtype), (0, pos % S_cache, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(
        cache_v, v.astype(cache_v.dtype), (0, pos % S_cache, 0, 0))
    # Ring-buffer cache: slots beyond `pos` are unwritten until the buffer
    # wraps (SWA archs allocate cache_len == window, so wrapping IS the
    # sliding window; RoPE is baked into cached k, and softmax is
    # permutation-invariant over slots, so ring order is harmless).
    idx = jnp.arange(S_cache)
    valid = (idx <= pos) | (pos >= S_cache)
    mask = jnp.where(valid, 0.0, -1e30).astype(jnp.float32)[None, None, None]
    out = gqa_scores_softmax_v(q, cache_k.astype(q.dtype),
                               cache_v.astype(q.dtype), mask,
                               cfg.n_kv_heads, cfg.attn_scale or None)
    return out.reshape(B, 1, -1) @ params["wo"], cache_k, cache_v


# --------------------------- MLPs ---------------------------

def init_mlp(key, cfg, d_ff: int | None = None) -> Params:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    dt = _dtype(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    if cfg.mlp in ("swiglu", "geglu"):
        return {"w_gate": _init(k1, (d, ff), dtype=dt),
                "w_up": _init(k2, (d, ff), dtype=dt),
                "w_down": _init(k3, (ff, d), dtype=dt)}
    return {"w_in": _init(k1, (d, ff), dtype=dt),
            "w_out": _init(k2, (ff, d), dtype=dt)}


def apply_mlp(params: Params, x: jnp.ndarray, cfg) -> jnp.ndarray:
    if "w_gate" in params:
        act = jax.nn.silu if cfg.mlp == "swiglu" else jax.nn.gelu
        return (act(x @ params["w_gate"]) * (x @ params["w_up"])) \
            @ params["w_down"]
    return jax.nn.gelu(x @ params["w_in"]) @ params["w_out"]


# --------------------------- embeddings / head ---------------------------

def init_embedding(key, cfg) -> Params:
    V = cfg.padded_vocab()
    dt = _dtype(cfg)
    k1, k2 = jax.random.split(key)
    p = {"tok": _init(k1, (V, cfg.d_model), scale=0.02, dtype=dt),
         "final_norm": jnp.ones((cfg.d_model,), dt)}
    if not cfg.tie_embeddings:
        p["head"] = _init(k2, (cfg.d_model, V), dtype=dt)
    return p


def embed(params: Params, tokens: jnp.ndarray,
          mult: float = 1.0) -> jnp.ndarray:
    x = params["tok"][tokens]
    return x if mult == 1.0 else x * mult


def head_weight(params: Params) -> jnp.ndarray:
    """(d, V): the LM head, or the token embedding where it is tied."""
    return params["head"] if "head" in params else params["tok"].T


def lm_logits(params: Params, x: jnp.ndarray,
              scaling: float = 1.0) -> jnp.ndarray:
    """Logits of the final-normed ``x``, divided by ``scaling``."""
    x = rms_norm(x, params["final_norm"])
    logits = x @ head_weight(params)
    return logits if scaling == 1.0 else logits / scaling
