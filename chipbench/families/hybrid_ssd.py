"""Operations, bytes and weight rules of the ``hybrid_ssd`` family: Mamba-2
SSD mixers with a GQA attention mixer at every layer i with
i % ``attn_period`` == ``attn_offset``, each mixer followed by a SwiGLU
MLP, the LM head tied to the token embedding (granite-4.0-h)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from lib.counts import BF16, vocab

F32 = 4
# the cache leaves that do not grow with the history
RECURRENT_LEAVES = ("state", "conv")


class Counts:
    """A granitemoehybrid decoder with no experts."""

    def __init__(self, c: dict) -> None:
        self.L = c["num_hidden_layers"]
        self.n_attn = sum(1 for i in range(self.L)
                          if i % c["attn_period"] == c["attn_offset"])
        self.n_ssd = self.L - self.n_attn
        self.d = c["hidden_size"]
        self.ff = c["intermediate_size"]
        self.hq = c["num_attention_heads"]
        self.hkv = c["num_key_value_heads"]
        self.hd = c["head_dim"]
        self.V = vocab(c)
        self.H = c["mamba_n_heads"]
        self.P = c["mamba_d_head"]
        self.N = c["mamba_d_state"]
        self.K = c["mamba_d_conv"]
        self.din = c["mamba_expand"] * self.d
        self.xbc = self.din + 2 * c["mamba_n_groups"] * self.N
        self.conv_bias = bool(c["mamba_conv_bias"])

    @property
    def ssd_matmul_params(self) -> int:
        """in_proj [z, x, B, C, dt] and out_proj of one SSD mixer."""
        return self.d * (self.din + self.xbc + self.H) + self.din * self.d

    @property
    def attn_matmul_params(self) -> int:
        q, kv = self.hq * self.hd, self.hkv * self.hd
        return 2 * self.d * q + 2 * self.d * kv

    @property
    def ssd_float32_params(self) -> int:
        """a_log, d_skip and dt_bias of every SSD mixer, kept in float32."""
        return self.n_ssd * 3 * self.H

    @property
    def matmul_params(self) -> int:
        return (self.n_ssd * self.ssd_matmul_params
                + self.n_attn * self.attn_matmul_params
                + self.L * 3 * self.d * self.ff + self.d * self.V)

    @property
    def n_params(self) -> int:
        ssd_rest = (self.K * self.xbc + self.xbc * self.conv_bias
                    + 3 * self.H + self.din)
        return (self.matmul_params + self.n_ssd * ssd_rest
                + self.L * 2 * self.d + self.d)

    def state_bytes(self, batch: int = 1) -> int:
        return self.n_ssd * batch * self.H * self.N * self.P * F32

    def conv_bytes(self, batch: int = 1) -> int:
        return self.n_ssd * batch * (self.K - 1) * self.xbc * BF16

    def kv_bytes(self, slots: int, batch: int = 1) -> int:
        return self.n_attn * batch * slots * self.hkv * self.hd * 2 * BF16

    def cache_bytes(self, slots: int, batch: int = 1) -> int:
        return (self.state_bytes(batch) + self.conv_bytes(batch)
                + self.kv_bytes(slots, batch))

    def decode_flops(self, slots: int, batch: int = 1) -> int:
        """One token per sequence against ``slots`` cached positions: every
        matrix product, the attention over the slots, and the SSD state
        update (decay, add the outer product) and readout."""
        attn = 2 * 2 * self.n_attn * self.hq * self.hd * slots
        ssd = 5 * self.n_ssd * self.H * self.N * self.P
        return batch * (2 * self.matmul_params + attn + ssd)

    def decode_bytes(self, slots: int, batch: int = 1) -> int:
        """Every weight once (the tied embedding read whole, as the head),
        one embedding row, the recurrent state and conv tail read and
        written, the K/V read at ``slots`` and one slot written."""
        weights = ((self.n_params - self.ssd_float32_params) * BF16
                   + self.ssd_float32_params * F32 + batch * self.d * BF16)
        recurrent = 2 * (self.state_bytes(batch) + self.conv_bytes(batch))
        return (weights + recurrent + self.kv_bytes(slots, batch)
                + self.kv_bytes(1, batch))


def _a_log(key, shape: tuple):
    """A = -exp(a_log) with -A uniform on [1, 16] (Mamba-2)."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))


def _dt_bias(key, shape: tuple):
    """softplus^-1(dt), dt log-uniform on [1e-3, 1e-1] (Mamba-2)."""
    u = jax.random.uniform(key, shape, jnp.float32, np.log(1e-3),
                           np.log(1e-1))
    dt = jnp.exp(u)
    return dt + jnp.log(-jnp.expm1(-dt))


def _ones(key, shape: tuple):
    return jnp.ones(shape, jnp.float32)


def _conv_bias(key, shape: tuple):
    return jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)


def _tok(key, shape: tuple):
    """The default embedding's N(0, 0.02) over the embedding multiplier
    (12): the input reaches the first layer at the default spread, and the
    tied head does not rank the input token's own row far above every
    other, as N(0, 0.02) does, where a random model repeats its input."""
    return jax.random.normal(key, shape, jnp.float32) * (0.02 / 12)


LEAF_RULES = {"a_log": _a_log, "dt_bias": _dt_bias, "d_skip": _ones,
              "out_norm": _ones, "conv_bias": _conv_bias, "tok": _tok}
