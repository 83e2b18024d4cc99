"""LM loss, chunked over sequence so (B, S, V) logits never materialise.

The head matmul + softmax-xent run per sequence chunk inside a lax.scan;
with the vocabulary sharded over the model axis, the log-sum-exp reduces
over a sharded dimension (GSPMD inserts the small all-reduce).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..models import layers as L

CHUNK = 512


def chunked_softmax_xent(hidden: jnp.ndarray, embed_params: dict,
                         labels: jnp.ndarray,
                         mask: jnp.ndarray | None = None,
                         chunk: int = CHUNK,
                         scaling: float = 1.0) -> jnp.ndarray:
    """hidden: (B, S, d); labels: (B, S) int32; mask: (B, S) or None;
    the logits are divided by ``scaling``.
    Returns mean masked token loss (fp32 scalar)."""
    B, S, d = hidden.shape
    if mask is None:
        mask = jnp.ones((B, S), jnp.float32)
    c = next(cc for cc in range(min(chunk, S), 0, -1) if S % cc == 0)
    nc = S // c

    hs = hidden.reshape(B, nc, c, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, nc, c).transpose(1, 0, 2)
    ms = mask.reshape(B, nc, c).transpose(1, 0, 2)

    def body(tot, inp):
        h, lab, m = inp
        h = L.rms_norm(h, embed_params["final_norm"])
        logits = (h @ L.head_weight(embed_params)).astype(jnp.float32)
        if scaling != 1.0:
            logits = logits / scaling
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
        return tot + jnp.sum((lse - gold) * m), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hs, ls, ms))
    return total / jnp.maximum(jnp.sum(mask), 1.0)


def lm_loss(params: dict, cfg, hidden: jnp.ndarray, tokens: jnp.ndarray,
            aux: jnp.ndarray, aux_weight: float = 0.01) -> jnp.ndarray:
    """Next-token loss. For VLM the hidden includes the prefix — only text
    positions predict."""
    if cfg.family == "vlm":
        hidden = hidden[:, cfg.n_prefix_tokens:]
    B, S = tokens.shape
    labels = jnp.concatenate([tokens[:, 1:], jnp.zeros((B, 1), tokens.dtype)],
                             axis=1)
    mask = jnp.concatenate([jnp.ones((B, S - 1), jnp.float32),
                            jnp.zeros((B, 1), jnp.float32)], axis=1)
    loss = chunked_softmax_xent(hidden, params["embed"], labels, mask,
                                scaling=cfg.logits_scaling)
    return loss + aux_weight * aux
