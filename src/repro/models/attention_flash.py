"""Blockwise (flash-style) attention in pure JAX.

Full-sequence attention at 32k+ cannot materialise (S, S) scores; this is
the online-softmax formulation: scan over KV blocks per Q block carrying
(running max, running sum, accumulator).  XLA keeps one (bq, bk) score
block live at a time.

Two iteration schemes:
* full rectangle (causal / bidirectional / prefix): every Q block visits
  every KV block; causal masking is applied per block.  For causal runs
  this computes ~2x the minimal FLOPs — a known baseline cost, listed as a
  hillclimb candidate in EXPERIMENTS.md §Perf.
* windowed (SWA / local attention): each Q block visits a statically-sized
  KV slice [start, start + window + bq) via dynamic_slice — O(S * window).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30


def _block_mask(qi0, ki0, bq, bk, *, causal: bool, window: int,
                prefix: int) -> jnp.ndarray:
    """Additive fp32 mask for a (bq, bk) block at global offsets (qi0, ki0)."""
    qi = qi0 + jnp.arange(bq)[:, None]
    ki = ki0 + jnp.arange(bk)[None, :]
    allow = jnp.ones((bq, bk), bool)
    if causal:
        allow &= ki <= qi
    if window:
        allow &= (qi - ki) < window
    if prefix:
        allow |= ki < prefix
    return jnp.where(allow, 0.0, NEG)


def _attend_block(q, k, v, mask, scale=None):
    """q: (B,Hkv,G,bq,D), k/v: (B,Hkv,bk,D), mask: (bq,bk).
    Returns (scores_exp (..bq,bk) style partials): m, l, acc contribution.
    Scores are scaled by ``scale``, else by 1/sqrt(D)."""
    s = jnp.einsum("bhgqd,bhkd->bhgqk", q, k,
                   preferred_element_type=jnp.float32)
    s = s / np.sqrt(q.shape[-1]) if scale is None else s * scale
    return s + mask


def blockwise_attention(q, k, v, n_kv: int, *, causal: bool = True,
                        window: int = 0, prefix: int = 0,
                        bq: int = 256, bk: int = 512,
                        scale: float | None = None) -> jnp.ndarray:
    """q: (B,S,Hq,D); k,v: (B,Sk,Hkv,D) -> (B,S,Hq,D).  fp32 accumulators."""
    B, S, Hq, D = q.shape
    Sk = k.shape[1]
    bq = min(bq, S)
    bk = min(bk, Sk)
    if S % bq or Sk % bk:      # smoke shapes: fall back to single block
        bq, bk = S, Sk
    G = Hq // n_kv
    nq, nk = S // bq, Sk // bk

    qb = q.reshape(B, nq, bq, n_kv, G, D).transpose(1, 0, 3, 4, 2, 5)
    kb = k.reshape(B, nk, bk, n_kv, D).transpose(1, 0, 3, 2, 4)
    vb = v.reshape(B, nk, bk, n_kv, D).transpose(1, 0, 3, 2, 4)

    use_window = bool(window) and Sk > (window + bq)

    def q_block(qi, qblk):
        # qblk: (B,Hkv,G,bq,D)
        m0 = jnp.full((B, n_kv, G, bq), NEG, jnp.float32)
        l0 = jnp.zeros((B, n_kv, G, bq), jnp.float32)
        a0 = jnp.zeros((B, n_kv, G, bq, D), jnp.float32)

        def kv_step(carry, inp):
            m, l, acc = carry
            ki0, kblk, vblk = inp
            mask = _block_mask(qi * bq, ki0, bq, kblk.shape[-2],
                               causal=causal, window=window, prefix=prefix)
            s = _attend_block(qblk, kblk, vblk, mask, scale)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            rescale = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l = l * rescale + jnp.sum(p, axis=-1)
            acc = acc * rescale[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p.astype(vblk.dtype), vblk
            ).astype(jnp.float32)
            return (m_new, l, acc), None

        if use_window:
            # statically-sized KV slice covering [q0 - window, q0 + bq)
            span = window + bq
            span = -(-span // bk) * bk
            start = jnp.clip(qi * bq + bq - span, 0, Sk - span)
            kfull = kb.transpose(1, 2, 0, 3, 4).reshape(B, n_kv, Sk, D)
            vfull = vb.transpose(1, 2, 0, 3, 4).reshape(B, n_kv, Sk, D)
            ksl = jax.lax.dynamic_slice(
                kfull, (0, 0, start, 0), (B, n_kv, span, D))
            vsl = jax.lax.dynamic_slice(
                vfull, (0, 0, start, 0), (B, n_kv, span, D))
            mask = _block_mask(qi * bq, start, bq, span, causal=causal,
                               window=window, prefix=prefix)
            s = _attend_block(qblk, ksl, vsl, mask, scale)
            m = jnp.max(s, axis=-1)
            p = jnp.exp(s - m[..., None])
            l = jnp.sum(p, axis=-1)
            acc = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(vsl.dtype),
                             vsl).astype(jnp.float32)
            out = acc / jnp.maximum(l, 1e-30)[..., None]
        else:
            ki0s = jnp.arange(nk) * bk
            (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                          (ki0s, kb, vb))
            out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out  # (B,Hkv,G,bq,D)

    outs = jax.lax.map(lambda args: q_block(*args),
                       (jnp.arange(nq), qb))          # (nq,B,Hkv,G,bq,D)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, S, Hq, D)
    return out.astype(q.dtype)
