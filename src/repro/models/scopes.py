"""Names of the device-side scopes that mark each layer of a step.

Each is a ``jax.named_scope``: it becomes part of the ``op_name`` of every
operation traced inside it, backward and rematerialised operations
included (``transpose(jvp(attn_core))``), and the profiler records that
name with each op of the device trace (its ``tf_op``).  The benchmark's
trace reduction (``benchmarks/chip/scopes.py``) matches these exact
strings to give device time per layer; an op under none of them counts
as "rest" (norms, the embedding, residual adds, copies).  Scopes are
metadata only: the compiled program is the same with or without them.
"""
from __future__ import annotations

ATTN_PROJ = "attn_proj"     # q/k/v/o projections, QKV bias, RoPE, transposes
ATTN_CORE = "attn_core"     # scores, mask, softmax, P.V
KV_WRITE = "kv_write"       # decode: the per-slot cache update
FFN = "ffn"                 # dense FFN, MoE, RWKV channel mix
HEAD = "head"               # vocabulary projection, log-softmax, NLL
OPTIMIZER = "optimizer"     # compression, global-norm clip, AdamW
SSM = "ssm"                 # Mamba mixer
RWKV = "rwkv"               # RWKV-6 time mix

ALL = (ATTN_PROJ, ATTN_CORE, KV_WRITE, FFN, HEAD, OPTIMIZER, SSM, RWKV)

# Scopes that nest with those above and take no op from them.  Inside FFN,
# the dropless MoE's two parts; an op under one of these is under FFN too,
# so FFN's time keeps its meaning.  Around the multi-token-prediction depth
# (its projection, its block and its head pass), MTP: the depth's ops keep
# their inner scopes (attn_core, ffn, head, ...) as well.
MOE_ROUTE = "moe_route"     # router, top-k, balance loss, sort, permute, combine
MOE_EXPERTS = "moe_experts"  # grouped matmuls and SwiGLU of the experts held
MTP = "mtp"                 # the multi-token-prediction depth, whole

NESTED = (MOE_ROUTE, MOE_EXPERTS, MTP)
