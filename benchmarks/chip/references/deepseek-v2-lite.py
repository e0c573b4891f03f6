"""Plain float32 reference of DeepSeek-V2-Lite, on one chip's share.

Straightforward ``jax.numpy`` with every matrix product at
``Precision.HIGHEST`` (through ``mm``), in the layout of the program's
parameter tree: ``embed/table``, ``lm_head/w``, ``final_norm/scale``, the
dense first layer under ``stack/prefix/blk0`` and the expert layers stacked
under ``stack/periods/sub0``.  It imports nothing of the program; from
``reference.py`` it takes ``highest_mm``, ``fp8_mm``, ``rmsnorm``,
``lr_at`` and ``decays``.  Sizes come from the configuration file.

Per layer (DeepSeek-V2's ``modeling_deepseek.py``, no q-LoRA):

    h = rmsnorm(x)
    q = h Wq                                  (S, H, nope + rope)
    c, kr = h Wkv_a                           (S, kv_lora), (S, rope)
    k_nope, v = rmsnorm(c) Wkv_b              (S, H, nope), (S, H, v)
    q_rope, kr = yarn_rope(q_rope), yarn_rope(kr)   (kr shared by heads)
    x = x + softmax(q.k * (nope + rope)^-1/2 * mscale(f, m_all)^2, causal) v Wo
    h = rmsnorm(x)
    layer 0:  x = x + (silu(h Wg) * h Wu) Wd            (dense, 10,944)
    else:     p = softmax(h Wr) over all E experts; the top K, gates not
              renormalised, times routed_scaling_factor;
              x = x + sum over the experts held e < E_held of
                  gate_e (silu(h Wg_e) * h Wu_e) Wd_e    (zero gate where
                  the token did not choose e)
                + (silu(h Wg_s) * h Wu_s) Wd_s           (shared experts)
    logits = rmsnorm(x) W_head                (untied, the vocabulary slice)

YaRN: inverse frequencies theta^(-2i/d) interpolated (divided by the
factor) below the correction dim of beta_slow, kept above that of
beta_fast, a linear ramp between; cos and sin times mscale(f, m) /
mscale(f, m_all); mscale(f, m) = 0.1 m ln f + 1.

The loss whose gradient trains is the mean next-token NLL plus
``aux_loss_alpha`` times each expert layer's sequence-wise balance loss,
E/(S K) sum_e count_e mean_s p_e, averaged over the sequences.

Departures from the published model, which the program shares: the rope
dims pair as rotate-half (first half with second half) where the source
pairs neighbours (an interleave of q's and k's rope columns before
rotating): the same function up to a fixed permutation of those weight
columns.  The experts not held here, and the layers cut, are left out
(the configuration file's deployment).  Device-level token dropping
(arXiv:2405.04434 section 3.2) is not done.

It runs in blocks so that it fits on one chip once the program's state is
freed: attention over blocks of queries, the head over blocks of
positions, each layer rematerialised under ``jax.grad``, one sequence at
a time.

(No ``from __future__ import annotations``: the harness loads this file
as a module it does not register, where a dataclass cannot resolve string
annotations.)
"""
import math
from dataclasses import dataclass
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import reference as base

f32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # attention queries per block
HEAD_BLOCK = 512       # positions per block of the head

highest_mm = base.highest_mm
fp8_mm = base.fp8_mm
rmsnorm = base.rmsnorm


@dataclass(frozen=True)
class RefConfig:
    d: int
    layers: int
    dense_layers: int
    heads: int
    nope: int
    rope: int
    vdim: int
    kv_lora: int
    vocab: int
    rope_theta: float
    eps: float
    experts: int          # the router's outputs
    held: int             # experts [0, held) computed here
    top_k: int
    norm_topk: bool
    routed_scale: float
    aux_alpha: float
    yarn: Tuple           # (factor, original max positions, beta_fast,
    #                        beta_slow, mscale, mscale_all_dim) or ()

    @classmethod
    def from_file(cls, c: Dict) -> "RefConfig":
        """Sizes as the program runs them: a key in ``reduced`` at the
        file's value, the published one beside it."""
        rs = c.get("rope_scaling") or {}
        yarn = (float(rs["factor"]), int(rs["original_max_position_embeddings"]),
                float(rs["beta_fast"]), float(rs["beta_slow"]),
                float(rs["mscale"]), float(rs["mscale_all_dim"])) \
            if rs.get("type") == "yarn" else ()
        published = c.get("published", {}).get("n_routed_experts",
                                               c["n_routed_experts"])
        return cls(d=c["hidden_size"], layers=c["num_hidden_layers"],
                   dense_layers=c["first_k_dense_replace"],
                   heads=c["num_attention_heads"],
                   nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"],
                   vdim=c["v_head_dim"], kv_lora=c["kv_lora_rank"],
                   vocab=c["vocab_size"], rope_theta=float(c["rope_theta"]),
                   eps=float(c["rms_norm_eps"]), experts=published,
                   held=c["n_routed_experts"],
                   top_k=c["num_experts_per_tok"],
                   norm_topk=bool(c["norm_topk_prob"]),
                   routed_scale=float(c["routed_scaling_factor"]),
                   aux_alpha=float(c["aux_loss_alpha"]), yarn=yarn)


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(c: RefConfig):
    """Inverse frequencies (rope/2,) and the factor on cos and sin."""
    d, base_ = c.rope, c.rope_theta
    extra = 1.0 / base_ ** (np.arange(0, d, 2, dtype=np.float64) / d)
    if not c.yarn:
        return jnp.asarray(extra, f32), 1.0
    factor, max_pos, fast, slow, ms, ms_all = c.yarn
    corr = lambda rot: d * math.log(max_pos / (rot * 2 * math.pi)) \
        / (2 * math.log(base_))
    low, high = max(math.floor(corr(fast)), 0), min(math.ceil(corr(slow)),
                                                    d - 1)
    high = high + 0.001 if low == high else high
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    inv = (extra / factor) * ramp + extra * (1 - ramp)
    return jnp.asarray(inv, f32), _mscale(factor, ms) / _mscale(factor, ms_all)


def softmax_scale(c: RefConfig) -> float:
    s = 1.0 / math.sqrt(c.nope + c.rope)
    if c.yarn and c.yarn[5]:
        s *= _mscale(c.yarn[0], c.yarn[5]) ** 2
    return s


def rope(c: RefConfig, x):
    """x: (S, H, rope); rotate-half pairing, positions 0..S-1."""
    inv, mult = yarn_inv_freq(c)
    ang = jnp.arange(x.shape[0], dtype=f32)[:, None] * inv[None, :]
    cos = (jnp.cos(ang) * mult)[:, None, :]
    sin = (jnp.sin(ang) * mult)[:, None, :]
    h = c.rope // 2
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(mm, c: RefConfig, p, h):
    S, H = h.shape[0], c.heads
    q = mm("sd,df->sf", h, p["wq"]["w"]).reshape(S, H, c.nope + c.rope)
    kva = mm("sd,df->sf", h, p["wkv_a"]["w"])
    ckv = rmsnorm(kva[:, :c.kv_lora], p["kv_norm"]["scale"], c.eps)
    kr = rope(c, kva[:, None, c.kv_lora:])                 # (S, 1, rope)
    kv = mm("sl,lf->sf", ckv, p["wkv_b"]["w"]).reshape(S, H, c.nope + c.vdim)
    k = jnp.concatenate([kv[..., :c.nope],
                         jnp.broadcast_to(kr, (S, H, c.rope))], -1)
    v = kv[..., c.nope:]
    q = jnp.concatenate([q[..., :c.nope], rope(c, q[..., c.nope:])], -1)
    scale = softmax_scale(c)
    nb = -(-S // Q_BLOCK)
    qb = jnp.pad(q, ((0, nb * Q_BLOCK - S), (0, 0), (0, 0))).reshape(
        nb, Q_BLOCK, H, c.nope + c.rope)
    kpos = jnp.arange(S)

    @jax.checkpoint
    def block(args):
        qc, start = args
        s = mm("qhd,khd->hqk", qc, k) * scale
        qpos = start + jnp.arange(Q_BLOCK)
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, -jnp.inf)
        return mm("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    o = jax.lax.map(block, (qb, jnp.arange(nb) * Q_BLOCK))
    o = o.reshape(nb * Q_BLOCK, H * c.vdim)[:S]
    return mm("sf,fd->sd", o, p["wo"]["w"])


def swiglu(mm, p, h):
    a = jax.nn.silu(mm("sd,df->sf", h, p["w_gate"]["w"]))
    return mm("sf,fd->sd", a * mm("sd,df->sf", h, p["w_up"]["w"]),
              p["w_down"]["w"])


def moe(mm, c: RefConfig, p, h):
    """(output, balance loss) of one expert layer on one sequence: every
    held expert runs on every token, at zero gate where not chosen."""
    S = h.shape[0]
    probs = jax.nn.softmax(mm("sd,de->se", h, p["router"]["w"]), -1)
    vals, idx = jax.lax.top_k(probs, c.top_k)
    if c.norm_topk:
        vals = vals / jnp.sum(vals, -1, keepdims=True)
    chosen = jax.nn.one_hot(idx, c.experts, dtype=f32)          # (S, K, E)
    gates = jnp.sum(chosen * vals[..., None], 1) * c.routed_scale
    a = jax.nn.silu(mm("sd,edf->esf", h, p["w_gate"]))
    y = mm("esf,efd->esd", a * mm("sd,edf->esf", h, p["w_up"]), p["w_down"])
    out = jnp.einsum("se,esd->sd", gates[:, :c.held], y, precision=HIGHEST)
    count = jnp.sum(chosen, (0, 1))                               # (E,)
    aux = jnp.sum(count * jnp.mean(probs, 0)) * c.experts / (S * c.top_k)
    return out + swiglu(mm, p["shared"], h), aux


def layer(mm, c: RefConfig, x, p):
    h = rmsnorm(x, p["norm1"]["scale"], c.eps)
    x = x + attention(mm, c, p["attn"], h)
    h = rmsnorm(x, p["norm2"]["scale"], c.eps)
    if "ffn_moe" in p:
        y, aux = moe(mm, c, p["ffn_moe"], h)
    else:
        y, aux = swiglu(mm, p["ffn"], h), jnp.zeros((), f32)
    return x + y, aux


def hidden(mm, c: RefConfig, W, tokens):
    """Final-norm hidden states (S, d) of one sequence, and the sum of its
    expert layers' balance losses."""
    x = jnp.take(W["embed"]["table"], tokens, axis=0).astype(f32)
    aux = jnp.zeros((), f32)
    for i in range(c.dense_layers):
        x, a = jax.checkpoint(partial(layer, mm, c))(
            x, W["stack"]["prefix"][f"blk{i}"])
        aux = aux + a
    x, auxes = jax.lax.scan(jax.checkpoint(partial(layer, mm, c)), x,
                            W["stack"]["periods"]["sub0"])
    return rmsnorm(x, W["final_norm"]["scale"], c.eps), aux + jnp.sum(auxes)


@partial(jax.jit, static_argnums=(0, 1))
def logits_at(mm, c: RefConfig, W, tokens, rows):
    """Logits (len(rows), vocab) at the given positions of one sequence."""
    h = hidden(mm, c, W, tokens)[0][rows]
    return mm("sd,dv->sv", h, W["lm_head"]["w"])


@partial(jax.jit, static_argnums=(0, 1))
def gaps_at(mm, c: RefConfig, W, tokens, rows, chosen):
    lg = logits_at(mm, c, W, tokens, rows)
    return jnp.max(lg, -1) - jnp.take_along_axis(lg, chosen[:, None], 1)[:, 0]


@partial(jax.jit, static_argnums=(0, 1))
def argmax_at(mm, c: RefConfig, W, tokens, rows):
    return jnp.argmax(logits_at(mm, c, W, tokens, rows), -1).astype(jnp.int32)


def nll_sum(mm, c: RefConfig, W, tokens):
    """(sum of next-token NLLs over one sequence, its balance loss)."""
    h, aux = hidden(mm, c, W, tokens)
    h, tgt = h[:-1], tokens[1:]
    nb = -(-h.shape[0] // HEAD_BLOCK)
    pad = nb * HEAD_BLOCK - h.shape[0]
    hb = jnp.pad(h, ((0, pad), (0, 0))).reshape(nb, HEAD_BLOCK, -1)
    tb = jnp.pad(tgt, (0, pad)).reshape(nb, HEAD_BLOCK)
    vb = jnp.pad(jnp.ones(tgt.shape, f32), (0, pad)).reshape(nb, HEAD_BLOCK)

    @jax.checkpoint
    def block(carry, args):
        hc, tc, vc = args
        lp = jax.nn.log_softmax(mm("sd,dv->sv", hc, W["lm_head"]["w"]), -1)
        nll = -jnp.take_along_axis(lp, tc[:, None], 1)[:, 0]
        return carry + jnp.sum(nll * vc), None

    tot, _ = jax.lax.scan(block, jnp.zeros((), f32), (hb, tb, vb))
    return tot, aux


@partial(jax.jit, static_argnums=(0, 1))
def sequence_grad(mm, c: RefConfig, W, tokens, count, batch):
    """Gradient of this sequence's share of the step's loss (its NLL sum
    over ``count`` targets, its balance loss over ``batch`` sequences),
    and its NLL sum."""
    def share(w):
        tot, aux = nll_sum(mm, c, w, tokens)
        return tot / count + c.aux_alpha * aux / batch, tot
    g, tot = jax.grad(share, has_aux=True)(W)
    return g, tot


@partial(jax.jit, donate_argnums=(0, 1))
def _add(acc, g):
    return jax.tree.map(jnp.add, acc, g)


@partial(jax.jit, static_argnums=(0,), donate_argnums=(1, 2, 3, 4))
def adamw(opt_items: Tuple, W, m, v, g, step, lr):
    """One AdamW step as the recipe states it (global-norm clip, bias
    correction, decay off norm scales); all four trees donated.  Returns
    (W, m, v, clipped g)."""
    opt = dict(opt_items)
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(norm, 1e-9))
    g = jax.tree.map(lambda x: x * scale, g)
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    bc1, bc2 = 1.0 - b1 ** step, 1.0 - b2 ** step
    paths = base._paths(W)

    def upd(p, gi, mi, vi, path):
        mi = b1 * mi + (1 - b1) * gi
        vi = b2 * vi + (1 - b2) * gi * gi
        delta = (mi / bc1) / (jnp.sqrt(vi / bc2) + eps)
        if base.decays(path):
            delta = delta + opt["weight_decay"] * p
        return p - lr * delta, mi, vi

    out = jax.tree.map(upd, W, g, m, v, paths)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2), g


def train(mm, c: RefConfig, W, batches, opt: Dict, steps: int):
    """``steps`` AdamW steps from the float32 weights ``W`` (donated) on
    ``batches[i]`` ((B, S) int32 each).  Returns (mean NLL of each step,
    the first clipped gradient as host arrays, weights).  At most the
    weights, both moments and one gradient are on the device at once."""
    m = v = None
    losses, g1 = [], None
    opt_items = tuple(sorted(opt.items()))
    for i in range(steps):
        toks = batches[i]
        B = toks.shape[0]
        count = float(B * (toks.shape[1] - 1))
        g, tot = None, 0.0
        for r in range(B):
            gr, t = sequence_grad(mm, c, W, toks[r], count, float(B))
            g = gr if g is None else _add(g, gr)
            tot += float(t)
        losses.append(tot / count)
        if m is None:
            m = jax.tree.map(jnp.zeros_like, W)
            v = jax.tree.map(jnp.zeros_like, W)
        W, m, v, g = adamw(opt_items, W, m, v, g, float(i + 1),
                           base.lr_at(opt, i + 1))
        if i == 0:
            g1 = jax.device_get(g)
        del g
    return losses, g1, W
