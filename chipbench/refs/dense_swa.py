"""Plain reference of a Mistral-style decoder: GQA, RoPE (half-split),
sliding-window causal attention, SwiGLU MLP, RMSNorm, separate LM head.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``; no cache, no kernels, no batching.  It reads its
sizes from the configuration file and its weights from
``chipbench/lib/weights.py`` by its own table of leaf paths.  With
``lowp=True`` every matrix product instead rounds both operands to
float8 (e4m3, one scale per row or column), the precision below the
configuration's bfloat16: the control that the comparison has to fail.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def sizes(c: dict) -> dict:
    return {"L": c["num_hidden_layers"], "d": c["hidden_size"],
            "ff": c["intermediate_size"], "hq": c["num_attention_heads"],
            "hkv": c["num_key_value_heads"], "hd": c["head_dim"],
            "V": int(c.get("padded_vocab_size", c["vocab_size"])),
            "window": int(c.get("sliding_window") or 0),
            "theta": float(c["rope_theta"]), "eps": float(c["rms_norm_eps"])}


def layout(c: dict) -> tuple:
    """The weights' (path, shape, dtype), stacked over layers."""
    s = sizes(c)
    L, d, ff, V = s["L"], s["d"], s["ff"], s["V"]
    q, kv = s["hq"] * s["hd"], s["hkv"] * s["hd"]
    dt = c["param_dtype"]
    table = {
        "/blocks/attn/wk": (L, d, kv), "/blocks/attn/wo": (L, q, d),
        "/blocks/attn/wq": (L, d, q), "/blocks/attn/wv": (L, d, kv),
        "/blocks/mlp/w_down": (L, ff, d), "/blocks/mlp/w_gate": (L, d, ff),
        "/blocks/mlp/w_up": (L, d, ff), "/blocks/norm1": (L, d),
        "/blocks/norm2": (L, d), "/embed/final_norm": (d,),
        "/embed/head": (d, V), "/embed/tok": (V, d),
    }
    return tuple((p, table[p], dt) for p in sorted(table))


def _q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, lowp):
    if lowp:
        a, b = _q8(a, -1), _q8(b, -2)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x: (S, H, D); rotates pairs (i, i + D/2)."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, D, 2, dtype=np.float32) / D))
    ang = pos[:, None].astype(jnp.float32) * inv          # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, s, lowp, block):
    """q: (S, hq, D), k/v: (S, hkv, D); causal, sliding window."""
    S, hq, D = q.shape
    G = hq // s["hkv"]
    kk = jnp.repeat(k, G, axis=1)                         # head h -> h // G
    vv = jnp.repeat(v, G, axis=1)
    if lowp:
        kk, vv = _q8(kk, -1), _q8(vv, 0)
    j = jnp.arange(S)[None, :]

    def one(qi):
        qb, i0 = qi
        if lowp:
            qb = _q8(qb, -1)
        i = i0 + jnp.arange(block)[:, None]
        ok = j <= i
        if s["window"]:
            ok &= (i - j) < s["window"]
        sc = jnp.einsum("qhd,khd->hqk", qb, kk, precision=HIGHEST) \
            / np.sqrt(D)
        sc = jnp.where(ok[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        if lowp:
            p = _q8(p, -1)
        return jnp.einsum("hqk,khd->qhd", p, vv, precision=HIGHEST)

    nb = S // block
    out = jax.lax.map(one, (q.reshape(nb, block, hq, D),
                            jnp.arange(nb) * block))
    return out.reshape(S, hq, D)


@functools.partial(jax.jit, static_argnames=("c_items", "lowp", "block"))
def _logits(params, tokens, positions, c_items, lowp=False, block=512):
    s = dict(c_items)
    f32 = jnp.float32
    S = tokens.shape[0]
    pos = jnp.arange(S)
    x = params["embed"]["tok"][tokens].astype(f32)

    def layer(x, p):
        p = jax.tree.map(lambda a: a.astype(f32), p)
        h = _rms(x, p["norm1"], s["eps"])
        q = _mm(h, p["attn"]["wq"], lowp).reshape(S, s["hq"], s["hd"])
        k = _mm(h, p["attn"]["wk"], lowp).reshape(S, s["hkv"], s["hd"])
        v = _mm(h, p["attn"]["wv"], lowp).reshape(S, s["hkv"], s["hd"])
        q, k = _rope(q, pos, s["theta"]), _rope(k, pos, s["theta"])
        o = _attention(q, k, v, s, lowp, block).reshape(S, -1)
        x = x + _mm(o, p["attn"]["wo"], lowp)
        h = _rms(x, p["norm2"], s["eps"])
        m = p["mlp"]
        g = jax.nn.silu(_mm(h, m["w_gate"], lowp)) * _mm(h, m["w_up"], lowp)
        return x + _mm(g, m["w_down"], lowp), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    h = _rms(x[positions], params["embed"]["final_norm"].astype(f32),
             s["eps"])
    return _mm(h, params["embed"]["head"].astype(f32), lowp)


def logits_at(params, config: dict, tokens, positions, pad_to: int,
              lowp: bool = False):
    """Float32 logits at ``positions`` of the sequence ``tokens``, the
    sequence zero-padded at its end to ``pad_to`` (causal: the padding
    changes no earlier position)."""
    tokens = np.asarray(tokens, np.int32)
    if tokens.size > pad_to:
        raise ValueError(f"sequence of {tokens.size} > pad {pad_to}")
    padded = np.zeros(pad_to, np.int32)
    padded[: tokens.size] = tokens
    block = min(512, pad_to)
    if pad_to % block:
        raise ValueError(f"pad {pad_to} is not a multiple of {block}")
    c_items = tuple(sorted(sizes(config).items()))
    return _logits(params, jnp.asarray(padded),
                   jnp.asarray(np.asarray(positions, np.int32)),
                   c_items, lowp=lowp, block=block)
