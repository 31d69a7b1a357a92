"""Operations and bytes the algorithm needs, from a configuration file's
sizes.  These count the model's mathematics, never what an implementation
happens to read or recompute, so a metric built on them reads the same work
whatever computes it.  One multiply-add is two operations.
"""
from __future__ import annotations


def matmul_params(c: dict) -> int:
    """Weights that take part in a matrix product for every token: the
    blocks' projections and MLP, the LM head and the value head (the
    embedding is a lookup)."""
    d, hd = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * c["intermediate_size"]
    return c["num_hidden_layers"] * per_layer + d * c["vocab_size"] + d


def attention_flops(c: dict, context: float) -> float:
    """Scores and weighted values of one query over ``context`` keys, in
    every layer."""
    return (4.0 * c["num_attention_heads"] * c["head_dim"] * context
            * c["num_hidden_layers"])


def forward_flops(c: dict, tokens: float, context_sum: float) -> float:
    """Forward operations of ``tokens`` tokens whose contexts add up to
    ``context_sum`` keys."""
    return 2.0 * matmul_params(c) * tokens + attention_flops(c, context_sum)


def causal_context_sum(seq: int) -> float:
    """Keys seen by all queries of one causal sequence: 1 + 2 + ... + S."""
    return seq * (seq + 1) / 2.0
