"""Operations and bytes the algorithm needs, from a configuration's sizes.

Counted from the shapes alone, never from the program. Attention counts
only the positions a token may attend to (causal prefill, the valid cache
of a decode step), so a share of a peak computed from these stays under
100% when the time covers the work.
"""
from __future__ import annotations

from typing import Iterable


def layer_matmul_params(s) -> int:
    """Weights of one layer's matmuls (attention projections and MLP)."""
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    attn = s.d_model * q + 2 * s.d_model * kv + q * s.d_model
    mlp = (3 if s.glu else 2) * s.d_model * s.d_ff
    return attn + mlp


def body_params(s) -> int:
    return s.layers * layer_matmul_params(s)


def unembed_params(s) -> int:
    return s.d_model * s.vocab


def attn_flops(s, ctx: int) -> int:
    """Scores and weighted sum of one query over ``ctx`` keys, all layers."""
    return 4 * s.layers * s.heads * s.head_dim * ctx


def prefill_flops(s, prompt_len: int) -> int:
    """One prompt through every layer; the logits of its last token only."""
    causal = prompt_len * (prompt_len + 1) // 2
    return (2 * body_params(s) * prompt_len + 2 * unembed_params(s)
            + 4 * s.layers * s.heads * s.head_dim * causal)


def decode_flops(s, contexts: Iterable[int]) -> int:
    """One decode step: one token per live slot, each attending to its
    ``ctx`` valid positions (the new one included)."""
    per_tok = 2 * (body_params(s) + unembed_params(s))
    return sum(per_tok + attn_flops(s, c) for c in contexts)


def decode_bytes(s, contexts: Iterable[int], weight_bytes: int = 2,
                 kv_bytes: int = 2) -> int:
    """HBM bytes one decode step needs: every weight once, the valid KV of
    each live slot read, and each slot's new K and V written."""
    contexts = list(contexts)
    weights = (body_params(s) + unembed_params(s)
               + 2 * s.layers * s.d_model + s.d_model) * weight_bytes
    per_pos = 2 * s.layers * s.kv_heads * s.head_dim * kv_bytes
    embed_rows = len(contexts) * s.d_model * weight_bytes
    return weights + embed_rows + per_pos * (sum(contexts) + len(contexts))


def train_flops_per_token(s, seq: int) -> float:
    """Forward and backward (3 x forward) per token of a packed row of
    ``seq`` tokens; recomputation under remat does not count."""
    causal = seq * (seq + 1) / 2
    fwd = (2 * (body_params(s) + unembed_params(s)) * seq
           + 4 * s.layers * s.heads * s.head_dim * causal)
    return 3.0 * fwd / seq
