"""Serving step factories: prefill (prompt -> cache) and decode (one token).

These are the functions the decode_* / long_* dry-run cells lower, and what
the serving example drives with batched requests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..models import forward_decode, forward_prefill
from ..models import layers as L


def make_prefill_step(cfg, pad_to: int | None = None):
    def prefill_step(params, batch):
        hidden, cache = forward_prefill(params, cfg, batch, pad_to=pad_to)
        logits = L.lm_logits(params["embed"], hidden[:, -1:],
                             cfg.logits_scaling)
        return logits, cache
    return prefill_step


def make_decode_step(cfg, greedy: bool = True):
    def decode_step(params, cache, tokens, pos):
        hidden, cache = forward_decode(params, cfg, cache, tokens, pos)
        logits = L.lm_logits(params["embed"], hidden, cfg.logits_scaling)
        if greedy:
            next_tok = jnp.argmax(logits[:, -1], axis=-1)[:, None] \
                .astype(jnp.int32)
        else:
            next_tok = tokens
        return next_tok, logits, cache
    return decode_step


def measure_decode_s(arch: str = "deepseek-7b", batch: int = 8,
                     prefill_len: int = 32, iters: int = 8,
                     warmup: int = 2) -> float:
    """Wall-clock seconds of one jitted batched decode step (median over
    ``iters`` after ``warmup`` compilation/cache runs).

    This is where the serve benchmark's publish cadence comes from: the
    time a decode fleet actually computes between token steps, measured
    on the smoke variant of a real architecture — instead of a guessed
    ``--think`` constant.  Prefill runs once to build the KV cache the
    step consumes."""
    import time

    import numpy as np

    from ..configs import ARCHS, ShapeConfig, smoke_variant
    from ..models import init_model, make_inputs

    cfg = smoke_variant(ARCHS[arch])
    key = jax.random.PRNGKey(0)
    params = init_model(key, cfg)
    shape = ShapeConfig("serve-measure", int(prefill_len), int(batch),
                        "prefill")
    batch_in = make_inputs(key, cfg, shape)
    _hidden, cache = forward_prefill(params, cfg, batch_in)
    step = jax.jit(make_decode_step(cfg))
    tokens = batch_in["tokens"][:, -1:]
    pos = jnp.asarray(int(prefill_len), jnp.int32)
    for _ in range(max(1, int(warmup))):
        _tok, logits, _cache = step(params, cache, tokens, pos)
        jax.block_until_ready(logits)
    times = []
    for _ in range(max(1, int(iters))):
        t0 = time.perf_counter()
        _tok, logits, _cache = step(params, cache, tokens, pos)
        jax.block_until_ready(logits)
        times.append(time.perf_counter() - t0)
    return float(np.median(times))
