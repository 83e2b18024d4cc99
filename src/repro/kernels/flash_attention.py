"""Pallas TPU flash attention (forward + backward) — hillclimb H3.

Beyond-paper perf kernel (EXPERIMENTS.md §Perf): the XLA-level flash
attention keeps O(S^2) score blocks flowing through HBM (30/33 baseline
cells are memory-bound on exactly that traffic).  On TPU the fix is
structural: hold the (bq, bk) score block in VMEM for its whole lifetime.
HBM traffic then collapses to the q/k/v/out (+dq/dk/dv) streams — which is
what the roofline analyzer counts for a custom call (operands + results),
making the dry-run numbers faithful to the TPU execution model.

Layout notes (MXU/VREG):
  * head_dim padded to a multiple of 128 by ops.py (zero pad is exact);
  * bq x bk = 256 x 512 default: s-block (256, 512) f32 = 512 KiB VMEM,
    acc (256, 128k) f32 — comfortably under ~16 MiB VMEM with double
    buffering;
  * grid iterates kv-minor (forward) so the online-softmax scratch
    (m, l, acc) persists across the kv sweep of one q block; backward uses
    a q-minor sweep for dk/dv and kv-minor for dq, each with VMEM
    accumulators, flash-2 style.
  * causal / sliding-window / prefix-LM masks are built from iota + the
    grid position — no mask tensors in HBM.
  * the per-row statistics (lse, delta) travel as (B, n_kv, G, 1, S): a
    block's trailing two dims must be (8k, 128k) or the array's own, and
    (1, bq) over (1, S) is legal for any group width G, where (1, bq) over
    (G, S) is not.

Oracle: ``repro.models.attention_flash.blockwise_attention`` (pure jnp);
tests sweep shapes/masks in interpret mode, including gradients.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _mask_block(qi0, ki0, bq, bk, causal, window, prefix):
    qi = qi0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    ki = ki0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    allow = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        allow &= ki <= qi
    if window:
        allow &= (qi - ki) < window
    if prefix:
        allow |= ki < prefix
    return jnp.where(allow, 0.0, NEG).astype(jnp.float32)


# ======================================================================
# forward
# ======================================================================

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_sc, l_sc,
                *, causal, window, prefix, scale, bq, bk, nk):
    j = pl.program_id(4)
    i = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_sc[...] = jnp.full_like(m_sc, NEG)
        l_sc[...] = jnp.zeros_like(l_sc)

    q = q_ref[0, 0, 0].astype(jnp.float32)          # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)             # (bk, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = s + _mask_block(i * bq, j * bk, bq, bk, causal, window, prefix)

    m_prev = m_sc[...]
    l_prev = l_sc[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_prev * alpha + jnp.sum(p, axis=1)
    v = v_ref[0, 0].astype(jnp.float32)             # (bk, D)
    acc[...] = acc[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_sc[...] = m_new
    l_sc[...] = l_new

    @pl.when(j == nk - 1)
    def _emit():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0, 0, 0] = (acc[...] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0, 0, 0] = (m_sc[...] + jnp.log(l)).astype(jnp.float32)


def flash_fwd_pallas(q, k, v, *, causal=True, window=0, prefix=0,
                     bq=256, bk=512, scale=None, interpret=True):
    """q: (B, n_kv, G, S, D); k, v: (B, n_kv, Sk, D). D % 128 == 0.
    Returns (out (B,n_kv,G,S,D), lse (B,n_kv,G,S))."""
    B, H, G, S, D = q.shape
    Sk = k.shape[2]
    bq = min(bq, S)
    bk = min(bk, Sk)
    assert S % bq == 0 and Sk % bk == 0
    nq, nk = S // bq, Sk // bk
    grid = (B, H, G, nq, nk)
    kern = functools.partial(_fwd_kernel, causal=causal, window=window,
                             prefix=prefix,
                             scale=scale if scale else 1.0 / np.sqrt(D),
                             bq=bq, bk=bk, nk=nk)
    out, lse = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, bq, D),
                         lambda b, h, g, i, j: (b, h, g, i, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, g, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, g, i, j: (b, h, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, bq, D),
                         lambda b, h, g, i, j: (b, h, g, i, 0)),
            pl.BlockSpec((1, 1, 1, 1, bq),
                         lambda b, h, g, i, j: (b, h, g, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, G, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, G, 1, S), jnp.float32),
        ],
        scratch_shapes=[
            # VMEM accumulators persist across the kv sweep
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out, lse.reshape(B, H, G, S)


# ======================================================================
# backward
# ======================================================================

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, dq_ref,
                   dq_acc, *, causal, window, prefix, scale, bq, bk, nk):
    j = pl.program_id(4)
    i = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q = q_ref[0, 0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0, 0, 0]
    dlt = dlt_ref[0, 0, 0, 0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = s + _mask_block(i * bq, j * bk, bq, bk, causal, window, prefix)
    p = jnp.exp(s - lse[:, None])
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - dlt[:, None]) * scale
    dq_acc[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _emit():
        dq_ref[0, 0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc,
                    *, causal, window, prefix, scale, bq, bk, nq, ng):
    i = pl.program_id(4)   # q block (minor)
    g = pl.program_id(3)   # q group
    j = pl.program_id(2)   # kv block

    @pl.when((i == 0) & (g == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0, 0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0, 0, 0]
    dlt = dlt_ref[0, 0, 0, 0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = s + _mask_block(i * bq, j * bk, bq, bk, causal, window, prefix)
    p = jnp.exp(s - lse[:, None])
    dv_acc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - dlt[:, None]) * scale
    dk_acc[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

    @pl.when((i == nq - 1) & (g == ng - 1))
    def _emit():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def flash_bwd_pallas(q, k, v, do, lse, delta, *, causal=True, window=0,
                     prefix=0, bq=256, bk=512, scale=None, interpret=True):
    """Gradients. Shapes as in flash_fwd_pallas; delta: (B,n_kv,G,S) f32."""
    B, H, G, S, D = q.shape
    Sk = k.shape[2]
    bq = min(bq, S)
    bk = min(bk, Sk)
    assert S % bq == 0 and Sk % bk == 0
    nq, nk = S // bq, Sk // bk
    scale = scale if scale else 1.0 / np.sqrt(D)
    lse = lse.reshape(B, H, G, 1, S)
    delta = delta.reshape(B, H, G, 1, S)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, window=window,
                          prefix=prefix, scale=scale, bq=bq, bk=bk, nk=nk),
        grid=(B, H, G, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, 1, bq, D),
                         lambda b, h, g, i, j: (b, h, g, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, g, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, g, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, 1, bq, D),
                         lambda b, h, g, i, j: (b, h, g, i, 0)),
            pl.BlockSpec((1, 1, 1, 1, bq),
                         lambda b, h, g, i, j: (b, h, g, 0, i)),
            pl.BlockSpec((1, 1, 1, 1, bq),
                         lambda b, h, g, i, j: (b, h, g, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, bq, D),
                               lambda b, h, g, i, j: (b, h, g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, G, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, window=window,
                          prefix=prefix, scale=scale, bq=bq, bk=bk,
                          nq=nq, ng=G),
        grid=(B, H, nk, G, nq),
        in_specs=[
            pl.BlockSpec((1, 1, 1, bq, D),
                         lambda b, h, j, g, i: (b, h, g, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, g, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, g, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, 1, bq, D),
                         lambda b, h, j, g, i: (b, h, g, i, 0)),
            pl.BlockSpec((1, 1, 1, 1, bq),
                         lambda b, h, j, g, i: (b, h, g, 0, i)),
            pl.BlockSpec((1, 1, 1, 1, bq),
                         lambda b, h, j, g, i: (b, h, g, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, g, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, g, i: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sk, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, Sk, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv
