"""Operations and bytes that DeepSeek-V2-Lite's steps need, on one chip's
share, from the configuration file's sizes (``flops.py``'s public
functions, and the held experts' grouped matmuls).

These count the work the mathematics requires, never what an
implementation happens to do.  Per layer: MLA with no q-LoRA (q, the
compressed kv and its rope key, the up-projection of the latent to keys
and values, the output), the dense first layers' SwiGLU, and in each
expert layer the router over all experts, the shared experts' SwiGLU and
the routed experts held here at the expected share of the assignments,
K * held / E a token (768 rows an expert at 8192 tokens: 6 of 64 experts a
token, 8 held).  Causal attention counts each (query, key) pair once:
q.k over nope + rope dims and p.v over v dims, per head.  Rematerialised
work does not count; a train step is forward and backward, 3x forward.
"""
from typing import Dict, Sequence


def _experts(c: Dict):
    """(router outputs, experts held here)."""
    return c.get("published", {}).get("n_routed_experts",
                                      c["n_routed_experts"]), \
        c["n_routed_experts"]


def _attn(c: Dict) -> int:
    d, h = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    kv = c["kv_lora_rank"]
    return d * h * qk + d * (kv + c["qk_rope_head_dim"]) \
        + kv * h * (c["qk_nope_head_dim"] + c["v_head_dim"]) \
        + h * c["v_head_dim"] * d


def _expert_params(c: Dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def _layers(c: Dict):
    """(dense layers, expert layers)."""
    dense = c["first_k_dense_replace"]
    return dense, c["num_hidden_layers"] - dense


def held_share(c: Dict) -> float:
    """Expected routed experts held here, per token: K * held / E."""
    e, held = _experts(c)
    return c["num_experts_per_tok"] * held / e


def _moe_fixed(c: Dict) -> int:
    """An expert layer's router and shared experts."""
    e, _ = _experts(c)
    return c["hidden_size"] * e + c["n_shared_experts"] * _expert_params(c)


def matmul_params(c: Dict, active: bool = True) -> int:
    """Parameters a token multiplies by: every layer's projections, the
    routed experts held here (at the expected share of a token's
    assignments when ``active``, all of them otherwise) and the head."""
    dense, moe = _layers(c)
    _, held = _experts(c)
    routed = held_share(c) if active else held
    return (dense + moe) * _attn(c) \
        + dense * 3 * c["hidden_size"] * c["intermediate_size"] \
        + moe * (_moe_fixed(c) + int(routed * _expert_params(c))) \
        + c["hidden_size"] * c["vocab_size"]


def total_params(c: Dict) -> int:
    """Every parameter held here (norm scales included)."""
    dense, moe = _layers(c)
    d = c["hidden_size"]
    norms = 2 * d + c["kv_lora_rank"]
    return (dense + moe) * (_attn(c) + norms) \
        + dense * 3 * d * c["intermediate_size"] \
        + moe * (_moe_fixed(c) + _experts(c)[1] * _expert_params(c)) \
        + 2 * d * c["vocab_size"] + d


def attention_pair_flops(c: Dict) -> int:
    """Forward FLOPs of one (query, key) pair over all layers and heads:
    q.k over nope + rope dims, p.v over v dims."""
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (2 * qk + 2 * c["v_head_dim"]) * c["num_attention_heads"] \
        * c["num_hidden_layers"]


def train_step_flops(c: Dict, batch: int, seq: int) -> float:
    """Forward and backward (3x forward) of one step of ``batch``
    sequences of ``seq`` tokens, causal."""
    fwd = 2 * matmul_params(c) * seq + attention_pair_flops(c) \
        * seq * (seq + 1) / 2
    return 3.0 * fwd * batch


def held_expert_rows(c: Dict, batch: int, seq: int) -> float:
    """Expected assignments of one step to the experts held, summed over
    the expert layers (what ``moe_held_assignments`` counts)."""
    return held_share(c) * batch * seq * _layers(c)[1]


def held_expert_train_flops(c: Dict, batch: int, seq: int) -> float:
    """The held experts' grouped matmuls in one train step: gate, up and
    down projections of every expected held row, forward and backward."""
    return 3.0 * 2 * _expert_params(c) * held_expert_rows(c, batch, seq)


def held_expert_train_bytes(c: Dict, batch: int, seq: int,
                            dtype_bytes: int = 2) -> float:
    """Bytes the held experts' grouped matmuls must move in one train step:
    forward, each expert layer reads its held weights, its rows in and
    writes them out (d wide), and writes and reads the SwiGLU's hidden
    rows (f wide); the backward moves twice that."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    _, held = _experts(c)
    weights = held * _expert_params(c) * _layers(c)[1]
    rows = held_expert_rows(c, batch, seq) * (2 * d + 2 * f)
    return 3.0 * (weights + rows) * dtype_bytes


def decode_step_flops(c: Dict, ctx: Sequence[int]) -> float:
    """One decode step: one token for each active slot, attending over
    that slot's ``ctx`` live positions."""
    return float(sum(2 * matmul_params(c) + attention_pair_flops(c) * n
                     for n in ctx))


def kv_bytes_per_token(c: Dict, dtype_bytes: int = 2) -> int:
    """MLA's cache: the compressed latent and the shared rope key."""
    return c["num_hidden_layers"] * (c["kv_lora_rank"]
                                     + c["qk_rope_head_dim"]) * dtype_bytes


def decode_step_bytes(c: Dict, ctx: Sequence[int],
                      dtype_bytes: int = 2) -> float:
    """One decode step: every weight held here read once, and each active
    slot's cache read at its live length and written at one position."""
    return float(total_params(c) * dtype_bytes
                 + sum(kv_bytes_per_token(c, dtype_bytes) * (n + 1)
                       for n in ctx))
