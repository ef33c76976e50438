"""Operations a sparse-expert decoder (``reference/moe_decoder.py``'s
``Spec``) needs, from its sizes alone, never from the program.

Only the experts a token is routed to count (k of E), and attention only
the positions a token may attend to, so a share of a peak computed from
these stays under 100% when the time covers the work.
"""
from __future__ import annotations


def expert_flops_per_row(s) -> int:
    """One routed (token, expert) row through one expert's SwiGLU FFN,
    forward: three matmuls of d x f."""
    return 3 * 2 * s.d_model * s.d_ff


def routed_train_flops_per_token(s) -> int:
    """The grouped products of a training step per token, forward and
    backward (3 x forward), over the k rows a token is routed to in every
    layer."""
    return 3 * s.layers * s.top_k * expert_flops_per_row(s)


def train_flops_per_token(s, seq: int) -> float:
    """Forward and backward (3 x forward) per token of a packed row of
    ``seq`` tokens: attention projections, router, the k routed experts,
    causal scores and the tied head; recomputation under remat does not
    count."""
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    proj = 2 * s.d_model * q + 2 * s.d_model * kv
    per_layer = 2 * (proj + s.d_model * s.experts) + s.top_k * expert_flops_per_row(s)
    causal = seq * (seq + 1) / 2
    fwd = ((s.layers * per_layer + 2 * s.d_model * s.vocab) * seq
           + 4 * s.layers * s.heads * s.head_dim * causal)
    return 3.0 * fwd / seq
