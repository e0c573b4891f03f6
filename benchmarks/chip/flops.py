"""Operations and bytes that a step needs, from a configuration file's sizes.

These count the work the mathematics requires, never what an
implementation happens to do: causal attention counts each (query, key)
pair once, a MoE layer counts its top-k experts per token, decoding reads
the KV cache at each slot's live length, and rematerialised work does not
count.  So no implementation can do the step in less time than
``max(flops / peak_flops, bytes / peak_bandwidth)``.
"""
from __future__ import annotations

from typing import Dict, Sequence


def _sizes(c: Dict):
    d, hd = c["hidden_size"], c["head_dim"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    if c.get("num_local_experts"):
        e, k, f = c["num_local_experts"], c["num_experts_per_tok"], \
            c["intermediate_size"]
        ffn_active, ffn_all = k * 3 * d * f + d * e, e * 3 * d * f + d * e
    else:
        ffn_active = ffn_all = 3 * d * c["intermediate_size"]
    return attn, ffn_active, ffn_all


def matmul_params(c: Dict, active: bool = True) -> int:
    """Parameters a token multiplies by: every layer's projections (the
    experts it is routed to, when ``active``) and the vocabulary head."""
    attn, ffn_active, ffn_all = _sizes(c)
    ffn = ffn_active if active else ffn_all
    return c["num_hidden_layers"] * (attn + ffn) \
        + c["hidden_size"] * c["vocab_size"]


def total_params(c: Dict) -> int:
    """Every parameter (norm scales and biases included)."""
    attn, _, ffn_all = _sizes(c)
    d, L = c["hidden_size"], c["num_hidden_layers"]
    bias = (c["num_attention_heads"] + 2 * c["num_key_value_heads"]) \
        * c["head_dim"] if c.get("attention_bias") else 0
    emb = d * c["vocab_size"] * (1 if c["tie_word_embeddings"] else 2)
    return L * (attn + ffn_all + bias + 2 * d) + emb + d


def attention_pair_flops(c: Dict) -> int:
    """Forward FLOPs of one (query, key) pair over all layers and heads:
    q.k and p.v, 2 * head_dim each."""
    return 4 * c["head_dim"] * c["num_attention_heads"] \
        * c["num_hidden_layers"]


def train_step_flops(c: Dict, batch: int, seq: int) -> float:
    """Forward and backward (3x forward) of one step of ``batch``
    sequences of ``seq`` tokens, causal."""
    fwd = 2 * matmul_params(c) * seq + attention_pair_flops(c) \
        * seq * (seq + 1) / 2
    return 3.0 * fwd * batch


def decode_step_flops(c: Dict, ctx: Sequence[int]) -> float:
    """One decode step: one token for each active slot, attending over
    that slot's ``ctx`` live positions."""
    return float(sum(2 * matmul_params(c) + attention_pair_flops(c) * n
                     for n in ctx))


def kv_bytes_per_token(c: Dict, dtype_bytes: int = 2) -> int:
    return 2 * c["num_hidden_layers"] * c["num_key_value_heads"] \
        * c["head_dim"] * dtype_bytes


def decode_step_bytes(c: Dict, ctx: Sequence[int],
                      dtype_bytes: int = 2) -> float:
    """One decode step: every weight read once (the embedding table once,
    as the head), and each active slot's cache read at its live length
    and written at one position."""
    return float(total_params(c) * dtype_bytes
                 + sum(kv_bytes_per_token(c, dtype_bytes) * (n + 1)
                       for n in ctx))
