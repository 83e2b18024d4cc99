"""Plain reference of granite-4.0-h (``granitemoehybrid`` with no experts):
Mamba-2 SSD and GQA attention mixers, each followed by a SwiGLU MLP.

Per layer: ``h = x + r * mixer(rms(x))``, then ``x' = h + r * mlp(rms(h))``
with ``r`` the residual multiplier; the embedding is multiplied by the
embedding multiplier, and the logits are the final-normed hidden state
times the transposed embedding (the head is tied) over the logits
scaling.  Attention is causal over the whole sequence with no positional
encoding and scores scaled by the attention multiplier.  The SSD mixer is
the literal token-by-token recurrence, ``s_t = exp(dt_t A) s_{t-1} +
dt_t B_t x_t^T`` and ``y_t = C_t^T s_t + D x_t`` (a ``lax.scan`` over
tokens), after the in-projection [z, x, B, C, dt], the causal depthwise
conv with its bias and SiLU, ``dt = softplus(dt + dt_bias)`` and
``A = -exp(a_log)``; its output is gated by SiLU(z), RMS-normed over all
channels and projected out.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``; no cache, no kernels, no batching.  The weights
stay in their stored type and each layer's are widened to float32 as the
layer runs: one scan over the SSD layers, each period's attention layer
run before the SSD layer that follows it.  It reads its sizes from the
configuration file and its weights from ``chipbench/lib/weights.py`` by
its own table of leaf paths.  With ``lowp=True`` every matrix product
instead rounds both operands to float8 (e4m3, one scale per row or
column), the precision below the configuration's bfloat16: the control
that the comparison has to fail.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def sizes(c: dict) -> dict:
    if c["mamba_n_groups"] != 1:
        raise ValueError("the reference shares B and C over all heads "
                         f"(n_groups 1), not {c['mamba_n_groups']}")
    d = c["hidden_size"]
    return {"L": c["num_hidden_layers"], "d": d,
            "ff": c["intermediate_size"], "hq": c["num_attention_heads"],
            "hkv": c["num_key_value_heads"], "hd": c["head_dim"],
            "V": int(c.get("padded_vocab_size", c["vocab_size"])),
            "period": c["attn_period"], "offset": c["attn_offset"],
            "H": c["mamba_n_heads"], "P": c["mamba_d_head"],
            "N": c["mamba_d_state"], "K": c["mamba_d_conv"],
            "din": c["mamba_expand"] * d,
            "eps": float(c["rms_norm_eps"]),
            "emb_mult": float(c["embedding_multiplier"]),
            "res_mult": float(c["residual_multiplier"]),
            "attn_scale": float(c["attention_multiplier"]),
            "logits_scaling": float(c["logits_scaling"])}


def layout(c: dict) -> tuple:
    """The weights' (path, shape, dtype), stacked over the layers of each
    kind in layer order."""
    s = sizes(c)
    L, d, ff, V, din, H = s["L"], s["d"], s["ff"], s["V"], s["din"], s["H"]
    na = sum(1 for i in range(L) if i % s["period"] == s["offset"])
    ns = L - na
    q, kv = s["hq"] * s["hd"], s["hkv"] * s["hd"]
    xbc = din + 2 * s["N"]
    dt = c["param_dtype"]
    table = {
        "/attn_blocks/attn/wk": (na, d, kv),
        "/attn_blocks/attn/wo": (na, q, d),
        "/attn_blocks/attn/wq": (na, d, q),
        "/attn_blocks/attn/wv": (na, d, kv),
        "/embed/final_norm": (d,), "/embed/tok": (V, d),
        "/ssd_blocks/ssm/conv": (ns, s["K"], xbc),
        "/ssd_blocks/ssm/conv_bias": (ns, xbc),
        "/ssd_blocks/ssm/out_norm": (ns, din),
        "/ssd_blocks/ssm/w_in": (ns, d, din + xbc + H),
        "/ssd_blocks/ssm/w_out": (ns, din, d),
    }
    for kind, n in (("attn_blocks", na), ("ssd_blocks", ns)):
        table.update({f"/{kind}/mlp/w_down": (n, ff, d),
                      f"/{kind}/mlp/w_gate": (n, d, ff),
                      f"/{kind}/mlp/w_up": (n, d, ff),
                      f"/{kind}/norm1": (n, d), f"/{kind}/norm2": (n, d)})
    f32 = {f"/ssd_blocks/ssm/{k}": (ns, H) for k in ("a_log", "d_skip",
                                                      "dt_bias")}
    out = [(p, table[p], dt) for p in table] + \
        [(p, f32[p], "float32") for p in f32]
    return tuple(sorted(out))


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


def _mlp(x, p, s, lowp):
    h = _rms(x, p["norm2"], s["eps"])
    m = p["mlp"]
    g = jax.nn.silu(_mm(h, m["w_gate"], lowp)) * _mm(h, m["w_up"], lowp)
    return x + s["res_mult"] * _mm(g, m["w_down"], lowp)


def _attention(x, p, s, lowp, block):
    """Causal attention over the whole sequence, no positional encoding."""
    S = x.shape[0]
    hq, hkv, hd = s["hq"], s["hkv"], s["hd"]
    a = p["attn"]
    h = _rms(x, p["norm1"], s["eps"])
    q = _mm(h, a["wq"], lowp).reshape(S, hq, hd)
    k = _mm(h, a["wk"], lowp).reshape(S, hkv, hd)
    v = _mm(h, a["wv"], lowp).reshape(S, hkv, hd)
    G = hq // hkv
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
        sc = jnp.einsum("qhd,khd->hqk", qb, kk, precision=HIGHEST) \
            * s["attn_scale"]
        sc = jnp.where((j <= i)[None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        if lowp:
            pr = _q8(pr, -1)
        return jnp.einsum("hqk,khd->qhd", pr, vv, precision=HIGHEST)

    nb = S // block
    o = jax.lax.map(one, (q.reshape(nb, block, hq, hd),
                          jnp.arange(nb) * block)).reshape(S, hq * hd)
    return x + s["res_mult"] * _mm(o, a["wo"], lowp)


def _ssd(x, p, s, lowp):
    """The SSD mixer as the literal recurrence over tokens."""
    S = x.shape[0]
    H, P, N, K, din = s["H"], s["P"], s["N"], s["K"], s["din"]
    m = p["ssm"]
    h = _rms(x, p["norm1"], s["eps"])
    proj = _mm(h, m["w_in"], lowp)
    z, xbc, dt = proj[:, :din], proj[:, din:2 * din + 2 * N], \
        proj[:, 2 * din + 2 * N:]
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    conv = m["conv_bias"] + sum(padded[i:i + S] * m["conv"][i]
                                for i in range(K))
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :din].reshape(S, H, P)
    Bs, Cs = xbc[:, din:din + N], xbc[:, din + N:]
    dt = jax.nn.softplus(dt + m["dt_bias"])                # (S, H)
    A = -jnp.exp(m["a_log"])

    def token(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = (jnp.exp(dt_t * A)[:, None, None] * state
                 + dt_t[:, None, None] * b_t[None, :, None] * x_t[:, None])
        y = jnp.einsum("n,hnp->hp", c_t, state, precision=HIGHEST)
        return state, y + m["d_skip"][:, None] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((H, N, P)), (xs, Bs, Cs, dt))
    y = y.reshape(S, din) * jax.nn.silu(z)
    y = _rms(y, m["out_norm"], s["eps"])
    return x + s["res_mult"] * _mm(y, m["w_out"], lowp)


@functools.partial(jax.jit, static_argnames=("c_items", "lowp", "block"))
def _logits(params, tokens, positions, c_items, lowp=False, block=512):
    s = dict(c_items)
    f32 = jnp.float32
    k1, o = s["period"] - 1, s["offset"]
    x = params["embed"]["tok"][tokens].astype(f32) * s["emb_mult"]

    def layer(blocks, i):
        return jax.tree.map(lambda a: a[i].astype(f32), params[blocks])

    def attention_layer(x, a):
        p = layer("attn_blocks", a)
        return _mlp(_attention(x, p, s, lowp, block), p, s, lowp)

    def ssd_layer(x, i):
        # the period's attention layer comes before its SSD layer ``o``
        x = jax.lax.cond(i % k1 == o, attention_layer, lambda x, a: x,
                         x, i // k1)
        p = layer("ssd_blocks", i)
        return _mlp(_ssd(x, p, s, lowp), p, s, lowp), None

    n_ssd = params["ssd_blocks"]["norm1"].shape[0]
    x, _ = jax.lax.scan(ssd_layer, x, jnp.arange(n_ssd))
    h = _rms(x[positions], params["embed"]["final_norm"].astype(f32),
             s["eps"])
    head = params["embed"]["tok"].astype(f32).T
    return _mm(h, head, lowp) / s["logits_scaling"]


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
