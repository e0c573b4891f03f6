"""Plain float32 reference of the benchmark's configurations.

Straightforward ``jax.numpy`` with every matrix product at
``Precision.HIGHEST``: no kernel, no cache, no batching of requests.  It
imports nothing of the program.  It reads the sizes from the benchmark's
configuration file and the weights from the tree that ``weights.py`` made,
in the program's layout (``embed/table``, ``final_norm/scale`` and the
per-layer tensors stacked on a leading axis under ``stack/periods/sub0``).

What it computes, per layer (pre-norm decoder, as Qwen2 and GraniteMoe):

    h = rmsnorm(x);  q, k, v = h Wq + bq, h Wk + bk, h Wv + bv
    q, k = rope(q), rope(k)            (rotate-half, theta from the config)
    x = x + softmax(q k^T / sqrt(hd), causal) v Wo   (GQA: kv heads shared)
    h = rmsnorm(x)
    x = x + (silu(h Wg) * h Wu) Wd                   (dense SwiGLU), or
    x = x + sum_top-k gate_e (silu(h Wg_e) * h Wu_e) Wd_e   (dropless MoE;
          gates = softmax over all experts, top k renormalised)
    logits = rmsnorm(x) E^T            (tied embedding head)

Departures from the published models, which the program shares: Granite's
embedding, residual, attention and logit multipliers are not applied (the
configuration file lists them under ``reduced``); nothing else.

It runs in blocks so that it fits on one chip beside nothing else:
attention over blocks of queries, the head over blocks of positions, one
sequence at a time, with each layer rematerialised under ``jax.grad``.

``mm`` is the matrix product.  ``highest_mm`` is the reference;
``fp8_mm`` rounds both operands to float8 (e4m3, one scale per operand)
first, which is the control: the next precision below the configuration's
bfloat16.
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

f32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # attention queries per block
HEAD_BLOCK = 512       # positions per block of the head


def highest_mm(spec: str, a, b):
    return jnp.einsum(spec, a.astype(f32), b.astype(f32), precision=HIGHEST,
                      preferred_element_type=f32)


def _round_fp8(x):
    x = x.astype(f32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(f32) * scale


@jax.custom_vjp
def _fp8(x):
    """x rounded to float8 e4m3 with one scale per tensor; under
    ``jax.grad`` the incoming gradient is rounded the same way, as a
    float8 training step would do."""
    return _round_fp8(x)


_fp8.defvjp(lambda x: (_round_fp8(x), None),
            lambda _, g: (_round_fp8(g),))


def fp8_mm(spec: str, a, b):
    return highest_mm(spec, _fp8(a), _fp8(b))


@dataclass(frozen=True)
class RefConfig:
    d: int
    ff: int
    layers: int
    heads: int
    kv_heads: int
    hd: int
    vocab: int
    rope_theta: float
    eps: float
    qkv_bias: bool
    experts: int = 0
    top_k: int = 0

    @classmethod
    def from_file(cls, c: Dict) -> "RefConfig":
        return cls(d=c["hidden_size"], ff=c["intermediate_size"],
                   layers=c["num_hidden_layers"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], hd=c["head_dim"],
                   vocab=c["vocab_size"], rope_theta=float(c["rope_theta"]),
                   eps=float(c["rms_norm_eps"]),
                   qkv_bias=bool(c["attention_bias"]),
                   experts=c.get("num_local_experts", 0),
                   top_k=c.get("num_experts_per_tok", 0))


def rmsnorm(x, scale, eps):
    x = x.astype(f32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(f32)


def rope(x, theta):
    """x: (S, H, hd); rotate-half convention, positions 0..S-1."""
    S, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=f32) / hd))
    ang = jnp.arange(S, dtype=f32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _proj(mm, h, p, bias: bool):
    y = mm("sd,df->sf", h, p["w"])
    return y + p["b"].astype(f32) if bias else y


def attention(mm, c: RefConfig, p, h):
    S = h.shape[0]
    g = c.heads // c.kv_heads
    q = _proj(mm, h, p["wq"], c.qkv_bias).reshape(S, c.kv_heads, g, c.hd)
    k = _proj(mm, h, p["wk"], c.qkv_bias).reshape(S, c.kv_heads, c.hd)
    v = _proj(mm, h, p["wv"], c.qkv_bias).reshape(S, c.kv_heads, c.hd)
    q = rope(q.reshape(S, c.heads, c.hd), c.rope_theta).reshape(
        S, c.kv_heads, g, c.hd)
    k = rope(k, c.rope_theta)
    nb = -(-S // Q_BLOCK)
    pad = nb * Q_BLOCK - S
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0))).reshape(
        nb, Q_BLOCK, c.kv_heads, g, c.hd)
    kpos = jnp.arange(S)

    @jax.checkpoint
    def block(args):
        qc, start = args
        s = mm("qngd,knd->ngqk", qc, k) / math.sqrt(c.hd)
        qpos = start + jnp.arange(Q_BLOCK)
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, -jnp.inf)
        return mm("ngqk,knd->qngd", jax.nn.softmax(s, -1), v)

    o = jax.lax.map(block, (qb, jnp.arange(nb) * Q_BLOCK))
    o = o.reshape(nb * Q_BLOCK, c.heads * c.hd)[:S]
    return mm("sf,fd->sd", o, p["wo"]["w"])


def swiglu(mm, p, h):
    a = jax.nn.silu(mm("sd,df->sf", h, p["w_gate"]["w"]))
    return mm("sf,fd->sd", a * mm("sd,df->sf", h, p["w_up"]["w"]),
              p["w_down"]["w"])


def moe(mm, c: RefConfig, p, h):
    """Dropless top-k MoE: every expert runs on every token, and the
    gates of the experts a token did not choose are zero."""
    probs = jax.nn.softmax(mm("sd,de->se", h, p["router"]["w"]), -1)
    vals, idx = jax.lax.top_k(probs, c.top_k)
    vals = vals / jnp.sum(vals, -1, keepdims=True)
    gates = jnp.sum(jax.nn.one_hot(idx, c.experts, dtype=f32)
                    * vals[..., None], axis=1)                  # (S, E)
    a = jax.nn.silu(mm("sd,edf->esf", h, p["w_gate"]))
    y = mm("esf,efd->esd", a * mm("sd,edf->esf", h, p["w_up"]), p["w_down"])
    return jnp.einsum("se,esd->sd", gates, y, precision=HIGHEST)


def layer(mm, c: RefConfig, x, p):
    h = rmsnorm(x, p["norm1"]["scale"], c.eps)
    x = x + attention(mm, c, p["attn"], h)
    h = rmsnorm(x, p["norm2"]["scale"], c.eps)
    y = moe(mm, c, p["ffn_moe"], h) if "ffn_moe" in p else \
        swiglu(mm, p["ffn"], h)
    return x + y


def hidden(mm, c: RefConfig, W, tokens):
    """Final-norm hidden states (S, d) of one sequence of token ids."""
    x = jnp.take(W["embed"]["table"], tokens, axis=0).astype(f32)
    body = jax.checkpoint(lambda x, p: (layer(mm, c, x, p), None))
    x, _ = jax.lax.scan(body, x, W["stack"]["periods"]["sub0"])
    return rmsnorm(x, W["final_norm"]["scale"], c.eps)


def _blocks(h, n):
    S = h.shape[0]
    nb = -(-S // n)
    return jnp.pad(h, ((0, nb * n - S),) + ((0, 0),) * (h.ndim - 1)), nb


@partial(jax.jit, static_argnums=(0, 1))
def logits_at(mm, c: RefConfig, W, tokens, rows):
    """Logits (len(rows), vocab) at the given positions of one sequence."""
    h = hidden(mm, c, W, tokens)[rows]
    return mm("sd,vd->sv", h, W["embed"]["table"])


@partial(jax.jit, static_argnums=(0, 1))
def gaps_at(mm, c: RefConfig, W, tokens, rows, chosen):
    """How far each chosen token's logit lies below the best logit, at the
    given positions: (best - logit[chosen]) per row."""
    lg = logits_at(mm, c, W, tokens, rows)
    return jnp.max(lg, -1) - jnp.take_along_axis(lg, chosen[:, None], 1)[:, 0]


@partial(jax.jit, static_argnums=(0, 1))
def argmax_at(mm, c: RefConfig, W, tokens, rows):
    """The token that ``mm``'s logits put first at each row."""
    return jnp.argmax(logits_at(mm, c, W, tokens, rows), -1).astype(jnp.int32)


def nll_sum(mm, c: RefConfig, W, tokens):
    """Sum of next-token negative log-likelihoods over one sequence, and
    the number of targets (S - 1)."""
    h = hidden(mm, c, W, tokens)[:-1]
    tgt = tokens[1:]
    hb, nb = _blocks(h, HEAD_BLOCK)
    tb, _ = _blocks(tgt, HEAD_BLOCK)
    valid, _ = _blocks(jnp.ones(tgt.shape, f32), HEAD_BLOCK)

    @jax.checkpoint
    def block(carry, args):
        hc, tc, vc = args
        lg = mm("sd,vd->sv", hc, W["embed"]["table"])
        lp = jax.nn.log_softmax(lg, -1)
        nll = -jnp.take_along_axis(lp, tc[:, None], 1)[:, 0]
        return carry + jnp.sum(nll * vc), None

    tot, _ = jax.lax.scan(
        block, jnp.zeros((), f32),
        (hb.reshape(nb, HEAD_BLOCK, -1), tb.reshape(nb, HEAD_BLOCK),
         valid.reshape(nb, HEAD_BLOCK)))
    return tot, tgt.shape[0]


@partial(jax.jit, static_argnums=(0, 1), donate_argnums=(3,))
def accumulate_grad(mm, c: RefConfig, W, acc, tokens):
    """acc + d(nll_sum)/dW for one sequence; returns (acc, nll_sum)."""
    tot, g = jax.value_and_grad(lambda w: nll_sum(mm, c, w, tokens)[0])(W)
    return jax.tree.map(jnp.add, acc, g), tot


# ---------------------------------------------------------------------------
# AdamW, as the training recipe states it
# ---------------------------------------------------------------------------


def lr_at(opt: Dict, step: int) -> float:
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    frac = min(max((step - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0), 1)
    if opt["schedule"] == "constant":
        decay = 1.0
    elif opt["schedule"] == "linear":
        decay = 1.0 - frac
    else:
        decay = 0.5 * (1.0 + math.cos(math.pi * frac))
    return opt["lr"] * warm * decay


def decays(path: str) -> bool:
    """Weight decay skips norm scales and biases."""
    return not re.search(r"(/scale|/b|/bias)$", path)


def _paths(tree, prefix=""):
    if isinstance(tree, dict):
        return {k: _paths(v, f"{prefix}/{k}") for k, v in tree.items()}
    return prefix


@partial(jax.jit, static_argnums=(0,), donate_argnums=(1, 2, 3))
def adamw(opt_items: Tuple, W, m, v, g, step, lr):
    opt = dict(opt_items)
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(norm, 1e-9))
    g = jax.tree.map(lambda x: x * scale, g)
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    bc1, bc2 = 1.0 - b1 ** step, 1.0 - b2 ** step
    paths = _paths(W)

    def upd(p, gi, mi, vi, path):
        mi = b1 * mi + (1 - b1) * gi
        vi = b2 * vi + (1 - b2) * gi * gi
        delta = (mi / bc1) / (jnp.sqrt(vi / bc2) + eps)
        if decays(path):
            delta = delta + opt["weight_decay"] * p
        return p - lr * delta, mi, vi

    out = jax.tree.map(upd, W, g, m, v, paths)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2), g


def train(mm, c: RefConfig, W, batches, opt: Dict, steps: int):
    """``steps`` AdamW steps from the float32 weights ``W`` (donated) on
    ``batches[i]`` ((B, S) int32 each), the mean loss over all targets of
    the batch.  Returns (losses, first clipped gradient, weights)."""
    zeros = lambda: jax.tree.map(jnp.zeros_like, W)
    m, v = zeros(), zeros()
    losses, g1 = [], None
    opt_items = tuple(sorted(opt.items()))
    for i in range(steps):
        toks = batches[i]
        acc, tot = zeros(), 0.0
        for r in range(toks.shape[0]):
            acc, t = accumulate_grad(mm, c, W, acc, toks[r])
            tot += float(t)
        count = toks.shape[0] * (toks.shape[1] - 1)
        g = jax.tree.map(lambda x: x / count, acc)
        del acc
        losses.append(tot / count)
        W, m, v, g = adamw(opt_items, W, m, v, g, float(i + 1),
                           lr_at(opt, i + 1))
        if i == 0:
            g1 = g
        else:
            del g
    return losses, g1, W
