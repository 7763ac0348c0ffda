"""Operations and bytes of the dense family only (named by
``bench/families/dense.py``), from its sizes alone.

The sizes come from a configuration file under ``bench/configs`` (or any
object with the same attribute names, such as the program's ``ModelConfig``).
A multiply-add counts as two operations.  Only the work the forward pass
needs is counted: the true vocabulary, not the padded one, and no padding
tokens.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Mapping, Tuple

KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
        "d_ff", "vocab_size", "tie_embeddings")


@dataclasses.dataclass(frozen=True)
class Dims:
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    tie_embeddings: bool
    compute_bytes: int = 2          # bf16 weights and KV as the step reads them

    @classmethod
    def of(cls, src) -> "Dims":
        get = src.get if isinstance(src, Mapping) else \
            (lambda k: getattr(src, k))
        return cls(**{k: (bool(get(k)) if k == "tie_embeddings" else
                          int(get(k))) for k in KEYS})

    # -- parameters -----------------------------------------------------------
    def layer_params(self) -> int:
        d, h, kv, hd = self.d_model, self.num_heads, self.num_kv_heads, \
            self.head_dim
        return 2 * d * h * hd + 2 * d * kv * hd + 3 * d * self.d_ff

    def head_params(self) -> int:
        return self.d_model * self.vocab_size

    # -- operations -----------------------------------------------------------
    def linear_flops(self) -> int:
        """Matmul operations of one token through every layer, no head."""
        return 2 * self.num_layers * self.layer_params()

    def attn_flops(self, ctx: int) -> int:
        """Scores and weighted values of one query over ``ctx`` keys."""
        return 4 * self.num_layers * self.num_heads * self.head_dim * ctx

    def head_flops(self) -> int:
        return 2 * self.head_params()

    def token_flops(self, ctx: int, head: bool = True) -> int:
        return self.linear_flops() + self.attn_flops(ctx) + \
            (self.head_flops() if head else 0)

    def request_flops(self, prompt: int, served: int) -> Tuple[int, int]:
        """(prefill, decode) operations of one request: ``prompt`` tokens
        prefilled causally with logits at the last one, then ``served - 1``
        decode steps (the last served token is never fed back)."""
        pre = prompt * self.linear_flops() + \
            self.attn_flops(prompt * (prompt + 1) // 2) + self.head_flops()
        n_dec = max(0, served - 1)
        # decode step j feeds the token at position prompt + j, which
        # attends over prompt + j + 1 keys
        keys = n_dec * (prompt + 1) + n_dec * (n_dec - 1) // 2
        dec = n_dec * (self.linear_flops() + self.head_flops()) + \
            self.attn_flops(keys)
        return pre, dec

    # -- bytes ----------------------------------------------------------------
    def weight_bytes(self) -> int:
        """Weights one decode step reads at the compute dtype: every layer
        and the output head (the embedding gather reads a few rows only)."""
        return (self.num_layers * self.layer_params() + self.head_params()) \
            * self.compute_bytes

    def kv_bytes_per_token(self) -> int:
        return 2 * self.num_layers * self.num_kv_heads * self.head_dim * \
            self.compute_bytes

    def decode_bytes(self, steps: int, contexts: Iterable[int]) -> int:
        """Least bytes of ``steps`` decode steps whose rows attended over
        ``contexts`` keys in all (one entry per row and step): the weights
        once a step, each row's cache, and the new token's K and V."""
        ctx = list(contexts)
        return steps * self.weight_bytes() + \
            (sum(ctx) + len(ctx)) * self.kv_bytes_per_token()
