"""Mamba2 / SSD (state-space duality) blocks.

The SSD layer computes, per head h with scalar decay a_t = exp(dt_t * A_h):

    s_t = a_t * s_{t-1} + dt_t * B_t x_t^T        (s: (N, P) state)
    y_t = C_t^T s_t + D_h x_t

Training/prefill uses the chunked block decomposition from the paper
(arXiv:2405.21060): quadratic attention-like compute *within* ssm_chunk-sized
chunks (masked by the decay kernel) + a linear `lax.scan` over chunk states.
That keeps everything as MXU einsums with O(S * Q) work instead of O(S^2),
and is exactly the TPU-native adaptation of the CUDA scan the paper ships.

Decode is the O(1) recurrence on a (B, H, N, P) state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import _dtype, _init, rms_norm


def init_ssm(key, cfg) -> dict:
    d = cfg.d_model
    din = cfg.ssm_expand * d
    H = cfg.ssm_heads
    N = cfg.ssm_state
    keys = jax.random.split(key, 8)
    dt = _dtype(cfg)
    p = {
        # fused input projection: [z (gate), x, B, C, dt]
        "w_in": _init(keys[0], (d, 2 * din + 2 * N + H), dtype=dt),
        "conv": _init(keys[1], (cfg.conv_width, din + 2 * N), scale=0.5,
                      dtype=dt),
        "a_log": jnp.zeros((H,), jnp.float32) - 0.5,
        "d_skip": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "w_out": _init(keys[2], (din, d), dtype=dt),
        "out_norm": jnp.ones((din,), dt),
    }
    if cfg.conv_bias:
        p["conv_bias"] = jnp.zeros((din + 2 * N,), dt)
    return p


def _split_proj(cfg, proj):
    din = cfg.ssm_expand * cfg.d_model
    N = cfg.ssm_state
    H = cfg.ssm_heads
    z, xin, Bc, Cc, dt = jnp.split(
        proj, [din, 2 * din, 2 * din + N, 2 * din + 2 * N], axis=-1)
    return z, xin, Bc, Cc, dt


def _causal_conv(x, w, state=None, bias=None):
    """x: (B, S, D); w: (K, D) depthwise causal conv, plus ``bias`` (D,)
    where given. If state (B, K-1, D) is given, runs in streaming mode and
    returns (y, new_state)."""
    K = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
        xp = jnp.concatenate([pad, x], axis=1)
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    y = sum(xp[:, i: i + x.shape[1]] * w[i] for i in range(K))
    if bias is not None:
        y = y + bias
    if state is None:
        return jax.nn.silu(y)
    return jax.nn.silu(y), xp[:, -(K - 1):]


def _segsum(log_a):
    """log_a: (..., Q). Returns (..., Q, Q) with L[i, j] = sum_{j<k<=i} log_a_k
    for i >= j, -inf above the diagonal."""
    Q = log_a.shape[-1]
    cs = jnp.cumsum(log_a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    i = jnp.arange(Q)[:, None]
    j = jnp.arange(Q)[None, :]
    return jnp.where(i >= j, diff, -jnp.inf)


def ssd_forward(params: dict, x: jnp.ndarray, cfg,
                initial_state: jnp.ndarray | None = None):
    """x: (B, S, d) -> (y (B, S, d), final_state (B, H, N, P), conv_tail
    (B, K-1, din+2N)). S must be a multiple of cfg.ssm_chunk (launch pads)."""
    B, S, d = x.shape
    H, N, P = cfg.ssm_heads, cfg.ssm_state, cfg.ssm_headdim
    # largest chunk <= ssm_chunk that divides S (production shapes divide
    # exactly; ragged test prompts degrade gracefully)
    Q = next(q for q in range(min(cfg.ssm_chunk, S), 0, -1) if S % q == 0)
    nC = S // Q

    proj = x @ params["w_in"]
    z, xin, Bc, Cc, dtp = _split_proj(cfg, proj)
    conv_in = jnp.concatenate([xin, Bc, Cc], axis=-1)
    K = params["conv"].shape[0]
    pad = jnp.zeros((B, max(0, K - 1 - S), conv_in.shape[-1]), conv_in.dtype)
    conv_tail = jnp.concatenate([pad, conv_in[:, -(K - 1):]], axis=1)
    conv_out = _causal_conv(conv_in, params["conv"],
                            bias=params.get("conv_bias"))
    xin, Bc, Cc = jnp.split(conv_out, [xin.shape[-1], xin.shape[-1] + N],
                            axis=-1)

    dt = jax.nn.softplus(dtp.astype(jnp.float32)
                         + params["dt_bias"])               # (B, S, H)
    A = -jnp.exp(params["a_log"])                           # (H,)
    log_a = (dt * A).reshape(B, nC, Q, H)                   # decay per step
    xh = xin.reshape(B, nC, Q, H, P)
    dth = dt.reshape(B, nC, Q, H)
    Bh = Bc.reshape(B, nC, Q, N).astype(jnp.float32)
    Ch = Cc.reshape(B, nC, Q, N).astype(jnp.float32)

    # ---- intra-chunk (quadratic within Q, fp32 accumulation) ----
    Lmat = jnp.exp(_segsum(log_a.transpose(0, 1, 3, 2)))    # (B,nC,H,Q,Q)
    scores = jnp.einsum("bcqn,bckn->bcqk", Ch, Bh)          # (B,nC,Q,Q)
    M = scores[:, :, None] * Lmat                           # (B,nC,H,Q,Q)
    M = M * dth.transpose(0, 1, 3, 2)[:, :, :, None, :]     # weight by dt_j
    y_intra = jnp.einsum("bchqk,bckhp->bcqhp", M,
                         xh.astype(jnp.float32))

    # ---- chunk states ----
    cums = jnp.cumsum(log_a, axis=2)                        # (B,nC,Q,H)
    decay_to_end = jnp.exp(cums[:, :, -1:, :] - cums)       # prod_{k>j} a_k
    state_c = jnp.einsum("bcqn,bcqh,bcqhp->bchnp",
                         Bh, (dth * decay_to_end).astype(jnp.float32),
                         xh.astype(jnp.float32))            # (B,nC,H,N,P)
    chunk_decay = jnp.exp(cums[:, :, -1, :])                # (B,nC,H)

    # ---- inter-chunk scan over chunk states ----
    h0 = (initial_state if initial_state is not None
          else jnp.zeros((B, H, N, P), jnp.float32))

    def step(h, inp):
        s_c, dec = inp                                      # (B,H,N,P),(B,H)
        h_new = h * dec[..., None, None] + s_c
        return h_new, h

    (h_final, h_prevs) = jax.lax.scan(
        step, h0, (state_c.transpose(1, 0, 2, 3, 4),
                   chunk_decay.transpose(1, 0, 2)))
    h_prevs = h_prevs.transpose(1, 0, 2, 3, 4)              # (B,nC,H,N,P)

    decay_in = jnp.exp(cums)                                # prod_{k<=q} a_k
    y_inter = jnp.einsum("bcqn,bcqh,bchnp->bcqhp",
                         Ch, decay_in, h_prevs)

    y = (y_intra + y_inter).reshape(B, S, H, P)
    y = y + params["d_skip"][None, None, :, None] \
        * xh.reshape(B, S, H, P).astype(jnp.float32)
    y = y.reshape(B, S, H * P).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), params["out_norm"])
    return y @ params["w_out"], h_final, conv_tail


def ssd_decode_step(params: dict, x: jnp.ndarray, cfg,
                    state: jnp.ndarray, conv_state: jnp.ndarray):
    """x: (B, 1, d); state: (B, H, N, P); conv_state: (B, K-1, din+2N).
    Returns (y (B,1,d), state', conv_state')."""
    B = x.shape[0]
    H, N, P = cfg.ssm_heads, cfg.ssm_state, cfg.ssm_headdim
    proj = x @ params["w_in"]
    z, xin, Bc, Cc, dtp = _split_proj(cfg, proj)
    conv_in = jnp.concatenate([xin, Bc, Cc], axis=-1)
    conv_out, conv_state = _causal_conv(conv_in, params["conv"], conv_state,
                                        params.get("conv_bias"))
    xin, Bc, Cc = jnp.split(conv_out, [xin.shape[-1], xin.shape[-1] + N],
                            axis=-1)
    dt = jax.nn.softplus(dtp.astype(jnp.float32)
                         + params["dt_bias"])[:, 0]          # (B, H)
    A = -jnp.exp(params["a_log"])
    a = jnp.exp(dt * A)                                      # (B, H)
    xh = xin.reshape(B, H, P).astype(jnp.float32)
    Bv = Bc[:, 0].astype(jnp.float32)                        # (B, N)
    Cv = Cc[:, 0].astype(jnp.float32)
    state = (state * a[..., None, None]
             + jnp.einsum("bn,bh,bhp->bhnp", Bv, dt, xh))
    y = jnp.einsum("bn,bhnp->bhp", Cv, state)
    y = y + params["d_skip"][None, :, None] * xh
    y = y.reshape(B, 1, H * P).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), params["out_norm"])
    return y @ params["w_out"], state, conv_state
