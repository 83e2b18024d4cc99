"""Architecture assembly: decoder-only LMs (dense / SWA / MoE / prefix-VLM),
encoder-decoder, Griffin hybrid, Mamba2 SSD stacks, and SSD/attention
hybrids (granite-4.0-h).

Layer stacks are `lax.scan`-ed over stacked parameter pytrees (one layer's
HLO regardless of depth — the only way 94-layer configs compile in
reasonable time on one CPU core) with optional remat per block.

Three entry points per family:
  forward_train(params, cfg, batch)        -> (hidden, aux_loss)
  forward_prefill(params, cfg, batch)      -> (hidden, cache)
  forward_decode(params, cfg, cache, tok, pos) -> (hidden, cache')
The LM head / loss live in train/loss.py (chunked over sequence so logits
never materialise at (B, S, V)).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import layers as L
from . import moe as M
from . import rglru as R
from . import ssm as S
from .attention_flash import blockwise_attention

Params = dict


# ======================================================================
# init
# ======================================================================

def _block_init(key, cfg, kind: str, tp_pad: int) -> Params:
    ks = jax.random.split(key, 6)
    dt = L._dtype(cfg)
    p: Params = {"norm1": jnp.ones((cfg.d_model,), dt)}
    if kind == "attn":
        p["attn"] = L.init_attention(ks[0], cfg, tp_pad)
        p["norm2"] = jnp.ones((cfg.d_model,), dt)
        p["mlp"] = L.init_mlp(ks[1], cfg)
    elif kind == "moe":
        p["attn"] = L.init_attention(ks[0], cfg, tp_pad)
        p["norm2"] = jnp.ones((cfg.d_model,), dt)
        p["moe"] = M.init_moe(ks[1], cfg)
    elif kind == "rec":
        p["rec"] = R.init_rglru_block(ks[0], cfg)
        p["norm2"] = jnp.ones((cfg.d_model,), dt)
        p["mlp"] = L.init_mlp(ks[1], cfg)
    elif kind == "local_attn":
        p["attn"] = L.init_attention(ks[0], cfg, tp_pad)
        p["norm2"] = jnp.ones((cfg.d_model,), dt)
        p["mlp"] = L.init_mlp(ks[1], cfg)
    elif kind == "ssd":  # hybrid_ssd: SSD mixer, then the MLP
        p["ssm"] = S.init_ssm(ks[0], cfg)
        p["norm2"] = jnp.ones((cfg.d_model,), dt)
        p["mlp"] = L.init_mlp(ks[1], cfg)
    elif kind == "ssm":
        p["ssm"] = S.init_ssm(ks[0], cfg)
        del p["norm1"]
        p["norm1"] = jnp.ones((cfg.d_model,), dt)
    elif kind == "cross":  # enc-dec decoder block
        p["attn"] = L.init_attention(ks[0], cfg, tp_pad)
        p["norm2"] = jnp.ones((cfg.d_model,), dt)
        p["xattn"] = L.init_attention(ks[1], cfg, tp_pad)
        p["norm3"] = jnp.ones((cfg.d_model,), dt)
        p["mlp"] = L.init_mlp(ks[2], cfg)
    else:
        raise ValueError(kind)
    return p


def _stack(key, cfg, kind: str, n: int, tp_pad: int) -> Params:
    keys = jax.random.split(key, n)
    ps = [_block_init(k, cfg, kind, tp_pad) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *ps)


def block_kinds(cfg) -> list[str]:
    """The block sequence of an architecture."""
    if cfg.family == "ssm":
        return ["ssm"] * cfg.n_layers
    if cfg.family == "moe":
        return ["moe"] * cfg.n_layers
    if cfg.family == "hybrid":
        return ["local_attn" if (i + 1) % cfg.attn_every == 0 else "rec"
                for i in range(cfg.n_layers)]
    if cfg.family == "hybrid_ssd":
        return ["attn" if i % cfg.attn_period == cfg.attn_offset else "ssd"
                for i in range(cfg.n_layers)]
    return ["attn"] * cfg.n_layers


def init_model(key, cfg, tp_pad: int = 1) -> Params:
    """tp_pad: the TP degree — q-heads are padded up to a multiple of it."""
    k_emb, k_blocks, k_enc = jax.random.split(key, 3)
    params: Params = {"embed": L.init_embedding(k_emb, cfg)}
    if cfg.family == "encdec":
        params["encoder"] = _stack(k_enc, cfg, "attn", cfg.enc_layers, tp_pad)
        params["decoder"] = _stack(k_blocks, cfg, "cross", cfg.dec_layers,
                                   tp_pad)
        return params
    kinds = block_kinds(cfg)
    if cfg.family == "hybrid":
        # stack per kind, preserving order at apply time via the kinds list
        n_rec = sum(1 for k in kinds if k == "rec")
        n_attn = len(kinds) - n_rec
        params["rec_blocks"] = _stack(jax.random.fold_in(k_blocks, 0), cfg,
                                      "rec", n_rec, tp_pad)
        params["attn_blocks"] = _stack(jax.random.fold_in(k_blocks, 1), cfg,
                                       "local_attn", n_attn, tp_pad)
        return params
    if cfg.family == "hybrid_ssd":
        n_attn = kinds.count("attn")
        params["ssd_blocks"] = _stack(jax.random.fold_in(k_blocks, 0), cfg,
                                      "ssd", len(kinds) - n_attn, tp_pad)
        params["attn_blocks"] = _stack(jax.random.fold_in(k_blocks, 1), cfg,
                                       "attn", n_attn, tp_pad)
        return params
    params["blocks"] = _stack(k_blocks, cfg, kinds[0], cfg.n_layers, tp_pad)
    return params


def param_shapes(cfg, tp_pad: int = 1):
    """ShapeDtypeStruct pytree without allocating (dry-run path)."""
    return jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg,
                                             tp_pad))


# ======================================================================
# block apply (full sequence)
# ======================================================================

def _apply_attn_block(p: Params, x, cfg, positions, *, n_heads, window=0,
                      prefix=0, causal=True, kv_override=None):
    h = L.rms_norm(x, p["norm1"])
    B, Sq, d = h.shape
    q = h @ p["attn"]["wq"]
    src = kv_override if kv_override is not None else h
    k = src @ p["attn"]["wk"]
    v = src @ p["attn"]["wv"]
    q = L._split_heads(q, n_heads, cfg.head_dim)
    k = L._split_heads(k, cfg.n_kv_heads, cfg.head_dim)
    v = L._split_heads(v, cfg.n_kv_heads, cfg.head_dim)
    if kv_override is None:
        q = L.apply_rope(q, positions, cfg.rotary_pct, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rotary_pct, cfg.rope_theta)
    if cfg.attn_scale and cfg.attn_impl != "flash":
        raise ValueError(f"attn_scale needs attn_impl 'flash', not "
                         f"{cfg.attn_impl!r}")
    if cfg.attn_impl == "flash_pallas":
        from ..kernels.ops import pallas_flash_attention
        out = pallas_flash_attention(q, k, v, cfg.n_kv_heads, causal,
                                     window, prefix, cfg.flash_bq,
                                     cfg.flash_bk)
    elif cfg.attn_impl == "flash_cvjp":
        from .attention_flash_vjp import flash_attention
        out = flash_attention(q, k, v, cfg.n_kv_heads, causal, window,
                              prefix, cfg.flash_bq, cfg.flash_bk)
    else:
        out = blockwise_attention(q, k, v, cfg.n_kv_heads, causal=causal,
                                  window=window, prefix=prefix,
                                  bq=cfg.flash_bq, bk=cfg.flash_bk,
                                  scale=cfg.attn_scale or None)
    x = _residual(x, out.reshape(B, Sq, -1) @ p["attn"]["wo"], cfg)
    return x, (k, v)


def _residual(x, y, cfg):
    """x + y, the sub-layer output ``y`` scaled by ``residual_mult``."""
    return x + (y if cfg.residual_mult == 1.0 else y * cfg.residual_mult)


def _apply_mlp_or_moe(p: Params, x, cfg, n_groups=1):
    aux = jnp.zeros((), jnp.float32)
    h = L.rms_norm(x, p["norm2"])
    if "moe" in p:
        y, aux = M.moe_ffn(p["moe"], h, cfg, n_groups=n_groups)
    else:
        y = L.apply_mlp(p["mlp"], h, cfg)
    return _residual(x, y, cfg), aux


def _dense_block(p, x, cfg, positions, *, n_heads, window, prefix,
                 n_groups=1, collect_kv=False):
    x, kv = _apply_attn_block(p, x, cfg, positions, n_heads=n_heads,
                              window=window, prefix=prefix)
    x, aux = _apply_mlp_or_moe(p, x, cfg, n_groups=n_groups)
    return x, aux, (kv if collect_kv else None)


def _rec_block(p, x, cfg, state=None, conv_state=None):
    h = L.rms_norm(x, p["norm1"])
    y, h_final, new_conv = R.rglru_block(p["rec"], h, cfg, state=state,
                                         conv_state=conv_state)
    x = x + y
    x, _ = _apply_mlp_or_moe(p, x, cfg)
    return x, h_final, new_conv


def _ssm_block(p, x, cfg, state=None):
    h = L.rms_norm(x, p["norm1"])
    y, final, conv_tail = S.ssd_forward(p["ssm"], h, cfg,
                                        initial_state=state)
    return x + y, (final, conv_tail)


# ======================================================================
# full-sequence forward (train / prefill)
# ======================================================================

def _sinusoidal(positions, d):
    pos = positions.astype(jnp.float32)[..., None]
    half = d // 2
    freq = jnp.exp(-np.log(10000.0) * jnp.arange(half) / half)
    ang = pos * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _embed_inputs(params, cfg, batch):
    """Returns (x (B,S,d), positions (B,S)). Handles frontend stubs."""
    if cfg.family == "vlm":
        tok_emb = L.embed(params["embed"], batch["tokens"])
        x = jnp.concatenate(
            [batch["prefix_emb"].astype(tok_emb.dtype), tok_emb], axis=1)
    else:
        x = L.embed(params["embed"], batch["tokens"], cfg.embed_mult)
    x = L.shard_batch(x)
    B, Sx = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(Sx)[None], (B, Sx))
    if cfg.rotary_pct == 0.0 and not cfg.nope:
        x = x + _sinusoidal(positions, cfg.d_model).astype(x.dtype)
    return x, positions


def _scan_stack(stack_params, fn, x, cfg, remat: bool):
    body = fn
    if remat:
        body = jax.checkpoint(fn,
                              policy=jax.checkpoint_policies.nothing_saveable)

    def step(carry, lp):
        x, aux = carry
        x2, aux2 = body(lp, x)
        x2 = L.shard_batch(x2)  # keep activations batch-sharded layer-on
        return (x2, aux + aux2), None

    (x, aux), _ = jax.lax.scan(step, (L.shard_batch(x),
                                      jnp.zeros((), jnp.float32)),
                               stack_params)
    return x, aux


def forward_train(params: Params, cfg, batch, n_groups: int = 1):
    """-> (hidden (B,S,d), aux_loss). S here includes any prefix tokens."""
    tp_pad_heads = params_n_heads(params, cfg)
    if cfg.family == "encdec":
        return _encdec_train(params, cfg, batch, tp_pad_heads)
    x, positions = _embed_inputs(params, cfg, batch)
    window = cfg.swa_window
    prefix = cfg.n_prefix_tokens if cfg.family == "vlm" else 0

    if cfg.family == "hybrid":
        return _hybrid_full(params, cfg, x, positions, tp_pad_heads), \
            jnp.zeros((), jnp.float32)

    if cfg.family == "hybrid_ssd":
        x, _ = hybrid_ssd_full(params, cfg, x, positions, tp_pad_heads,
                               remat=cfg.remat, cache=False)
        return x, jnp.zeros((), jnp.float32)

    if cfg.family == "ssm":
        def fn(lp, xx):
            y, _ = _ssm_block(lp, xx, cfg)
            return y, jnp.zeros((), jnp.float32)
        x, aux = _scan_stack(params["blocks"], fn, x, cfg, cfg.remat)
        return x, aux

    def fn(lp, xx):
        y, aux, _ = _dense_block(lp, xx, cfg, positions, n_heads=tp_pad_heads,
                                 window=window, prefix=prefix,
                                 n_groups=n_groups)
        return y, aux

    x, aux = _scan_stack(params["blocks"], fn, x, cfg, cfg.remat)
    return x, aux


def _hybrid_full(params, cfg, x, positions, n_heads):
    """Order-preserving interleave: scan rec blocks in runs, attention blocks
    unstacked-by-index via lax.switch-free gather (runs are uniform: pattern
    rec,rec,attn repeating), so we scan (rec,rec,attn) super-blocks and
    append the leftover rec blocks."""
    kinds = block_kinds(cfg)
    n_attn = sum(1 for k in kinds if k == "local_attn")
    n_super = n_attn                       # each super block = rec,rec,attn
    rec_p, attn_p = params["rec_blocks"], params["attn_blocks"]
    rec_used = 2 * n_super

    super_rec = jax.tree.map(
        lambda a: a[:rec_used].reshape(2, n_super, *a.shape[1:])
        .swapaxes(0, 1), rec_p)
    window = cfg.local_window

    def super_block(lp, xx):
        rp, ap = lp
        for i in range(2):
            sub = jax.tree.map(lambda a: a[i], rp)
            xx, _, _ = _rec_block(sub, xx, cfg)
        xx, _, _ = _dense_block(ap, xx, cfg, positions, n_heads=n_heads,
                                window=window, prefix=0)
        return xx, jnp.zeros((), jnp.float32)

    x, _ = _scan_stack((super_rec, attn_p), super_block, x, cfg, cfg.remat)

    n_left = len(kinds) - 3 * n_super
    if n_left:
        left = jax.tree.map(lambda a: a[rec_used:], rec_p)

        def leftover(lp, xx):
            y, _, _ = _rec_block(lp, xx, cfg)
            return y, jnp.zeros((), jnp.float32)
        x, _ = _scan_stack(left, leftover, x, cfg, cfg.remat)
    return x


def _encdec_train(params, cfg, batch, n_heads):
    enc_x = batch["src_emb"].astype(L._dtype(cfg))
    B, Se, d = enc_x.shape
    enc_pos = jnp.broadcast_to(jnp.arange(Se)[None], (B, Se))
    enc_x = enc_x + _sinusoidal(enc_pos, d).astype(enc_x.dtype)

    def enc_fn(lp, xx):  # bidirectional encoder
        xx, _ = _apply_attn_block(lp, xx, cfg, enc_pos, n_heads=n_heads,
                                  causal=False)
        xx, aux = _apply_mlp_or_moe(lp, xx, cfg)
        return xx, aux

    enc_out, _ = _scan_stack(params["encoder"], enc_fn, enc_x, cfg, cfg.remat)

    dec_x, dec_pos = _embed_inputs(params, cfg,
                                   {"tokens": batch["tokens"]})

    def dec_fn(lp, xx):
        xx, _ = _apply_attn_block(lp, xx, cfg, dec_pos, n_heads=n_heads,
                                  causal=True)
        xp = {"attn": lp["xattn"], "norm1": lp["norm3"]}
        xx, _ = _apply_attn_block(xp, xx, cfg, dec_pos, n_heads=n_heads,
                                  causal=False, kv_override=enc_out)
        xx, aux = _apply_mlp_or_moe(lp, xx, cfg)
        return xx, aux

    dec_out, aux = _scan_stack(params["decoder"], dec_fn, dec_x, cfg,
                               cfg.remat)
    return dec_out, aux


def params_n_heads(params: Params, cfg) -> int:
    """Recover the (possibly TP-padded) q-head count from the weights."""
    if cfg.family == "encdec":
        wq = params["decoder"]["attn"]["wq"]
    elif cfg.family in ("hybrid", "hybrid_ssd"):
        wq = params["attn_blocks"]["attn"]["wq"]
    elif cfg.family == "ssm":
        return 0
    else:
        wq = params["blocks"]["attn"]["wq"]
    return wq.shape[-1] // cfg.head_dim


# ======================================================================
# hybrid_ssd: SSD and attention mixers in a fixed period
# ======================================================================

def hybrid_ssd_stack(params: Params, cfg, x, ssd_fn, attn_fn, rec=None,
                     kv=None):
    """Runs the layer pattern as one ``lax.scan`` over its periods.  A
    period is ``attn_period - 1`` SSD layers, unrolled, with the period's
    attention layer applied before SSD layer ``attn_offset``.
    ``ssd_fn(x, p, c)`` and ``attn_fn(x, p, c)`` -> ``(x, c')`` apply one
    layer, ``c`` being that layer's slice of ``rec`` ((state, conv)) or
    ``kv`` ((k, v)), or ``None``.  The SSD weights and ``rec`` are indexed
    by the period's counter and ``kv`` is a scan input and output: the TPU
    compiler copies a stacked leaf whole where it is carried through the
    loop or a ``lax.cond``, reshaped into periods, or indexed by a second
    loop.  Returns (x, (rec', kv')): the ``c'`` of the SSD layers stacked
    in layer order, and of the attention layers."""
    k1, o = cfg.attn_period - 1, cfg.attn_offset
    n_per = cfg.n_layers // cfg.attn_period
    if cfg.n_layers % cfg.attn_period or not 0 <= o < k1:
        raise ValueError(f"{cfg.n_layers} layers in periods of "
                         f"{cfg.attn_period} with attention at {o}: not a "
                         f"whole number of periods, or attention last")

    def at(tree, i):
        return jax.tree.map(lambda a: a[i], tree)

    def period(xx, inp):
        j, ap, kc = inp
        outs = []
        for i in range(k1):
            if i == o:
                xx, kc = attn_fn(xx, ap, kc)
            ix = j * k1 + i
            xx, c = ssd_fn(xx, at(params["ssd_blocks"], ix), at(rec, ix))
            outs.append(c)
        return xx, (jax.tree.map(lambda *a: jnp.stack(a), *outs), kc)

    x, (rec, kv) = jax.lax.scan(
        period, x, (jnp.arange(n_per), params["attn_blocks"], kv))
    return x, (jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), rec), kv)


def hybrid_ssd_full(params: Params, cfg, x, positions, n_heads,
                    remat: bool = False, pad_to: int | None = None,
                    cache: bool = True):
    """Full-sequence hybrid_ssd stack (train / prefill) -> (x, cache): the
    SSD layers' final states and conv tails, and the attention layers' K/V
    zero-padded to ``pad_to`` slots (defaults to the sequence length);
    ``cache=False`` (training) keeps none."""
    slots = max(x.shape[1], pad_to or 0)

    def ssd_fn(xx, p, c):
        h = L.rms_norm(xx, p["norm1"])
        y, state, conv = S.ssd_forward(p["ssm"], h, cfg)
        xx, _ = _apply_mlp_or_moe(p, _residual(xx, y, cfg), cfg)
        return xx, ((state, conv) if cache else None)

    def attn_fn(xx, p, c):
        xx, (k, v) = _apply_attn_block(p, xx, cfg, positions,
                                       n_heads=n_heads)
        xx, _ = _apply_mlp_or_moe(p, xx, cfg)
        return xx, ((_fit_seq(k, slots), _fit_seq(v, slots)) if cache
                    else None)

    if remat:
        ssd_fn, attn_fn = jax.checkpoint(ssd_fn), jax.checkpoint(attn_fn)
    x, (rec, kv) = hybrid_ssd_stack(params, cfg, x, ssd_fn, attn_fn)
    if not cache:
        return x, None
    return x, {"state": rec[0], "conv": rec[1], "k": kv[0], "v": kv[1]}


def _fit_seq(k, slots: int):
    """(B, S, H, D) zero-padded to ``slots`` positions."""
    pad = slots - k.shape[1]
    return k if not pad else jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
