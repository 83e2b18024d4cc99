"""Operations and bytes of the ``dense`` family: a decoder-only
transformer with GQA and a SwiGLU MLP."""
from __future__ import annotations

from lib.counts import BF16, vocab


class Counts:
    """A decoder-only transformer with GQA and a SwiGLU MLP."""

    def __init__(self, c: dict) -> None:
        self.L = c["num_hidden_layers"]
        self.d = c["hidden_size"]
        self.ff = c["intermediate_size"]
        self.hq = c["num_attention_heads"]
        self.hkv = c["num_key_value_heads"]
        self.hd = c["head_dim"]
        self.V = vocab(c)

    @property
    def layer_matmul_params(self) -> int:
        d, q, kv = self.d, self.hq * self.hd, self.hkv * self.hd
        return d * q + 2 * d * kv + q * d + 3 * d * self.ff

    @property
    def matmul_params(self) -> int:
        return self.L * self.layer_matmul_params + self.d * self.V

    @property
    def n_params(self) -> int:
        return (self.L * (self.layer_matmul_params + 2 * self.d)
                + 2 * self.V * self.d + self.d)

    def cache_bytes(self, slots: int, batch: int = 1) -> int:
        return self.L * batch * slots * self.hkv * self.hd * 2 * BF16

    def decode_flops(self, slots: int, batch: int = 1) -> int:
        """One token per sequence against ``slots`` cached positions."""
        attn = 2 * 2 * self.L * self.hq * self.hd * slots
        return batch * (2 * self.matmul_params + attn)

    def decode_bytes(self, slots: int, batch: int = 1) -> int:
        """Every weight once, the whole cache read, one slot written."""
        weights = (self.n_params - self.V * self.d) * BF16 \
            + batch * self.d * BF16
        write = self.L * batch * self.hkv * self.hd * 2 * BF16
        return weights + self.cache_bytes(slots, batch) + write
