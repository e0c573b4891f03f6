"""Plain float32 reference of JoyAI-LLM-Flash (the DeepSeek-V3 block), on
one chip's share.

Straightforward ``jax.numpy`` with every matrix product at
``Precision.HIGHEST`` (through ``mm``), in the layout of the program's
parameter tree: ``embed/table``, ``lm_head/w``, ``final_norm/scale``, the
dense first layer under ``stack/prefix/blk0``, the expert layers stacked
under ``stack/periods/sub0`` and the multi-token-prediction depth under
``mtp``.  It imports nothing of the program; from ``reference.py`` it
takes ``highest_mm``, ``fp8_mm``, ``rmsnorm``, ``rope``, ``lr_at`` and
``decays``.  Sizes and the training constants come from the
configuration file.

Per layer (DeepSeek-V3, arXiv:2412.19437 section 2.1):

    h = rmsnorm(x)
    q = rmsnorm(h Wq_a) Wq_b                  (S, H, nope + rope)   q-LoRA
    c, kr = h Wkv_a                           (S, kv_lora), (S, rope)
    k_nope, v = rmsnorm(c) Wkv_b              (S, H, nope), (S, H, v)
    q_rope, kr = rope(q_rope), rope(kr)       (kr shared by heads)
    x = x + softmax(q.k * (nope + rope)^-1/2, causal) v Wo
    h = rmsnorm(x)
    layer 0:  x = x + (silu(h Wg) * h Wu) Wd            (dense, 7,168)
    else:     s = sigmoid(h Wr) over all E experts      (affinities)
              the top K of s + b (b: the router's correction bias, which
              no gradient reaches); gates s of those K over their sum,
              times routed_scaling_factor;
              x = x + sum over the experts held e < E_held of
                  gate_e (silu(h Wg_e) * h Wu_e) Wd_e    (zero gate where
                  the token did not choose e)
                + (silu(h Wg_s) * h Wu_s) Wd_s           (shared expert)
    logits = rmsnorm(x) W_head                (untied, the vocabulary slice)

The multi-token-prediction depth (section 2.2, one depth), from the last
layer's output x before the final norm: position i takes
W_eh [rmsnorm(Emb(t_{i+1})); rmsnorm(x_i)] through one more such expert
layer, then the final norm and the head, and predicts t_{i+2}.  It runs
over all S positions; the last, which has no next token, takes token 0.

The loss whose gradient trains is the mean next-token NLL, plus
``mtp_loss_weight`` times the depth's mean NLL over its S - 2 targets, plus
``aux_loss_alpha`` times each expert layer's (the depth's included)
sequence-wise balance loss, E/(S K) sum_e count_e mean_s s'_e, where
s' = s over its sum over the E experts and count_e counts the assignments
as routed, averaged over the sequences.  After AdamW (which, with its
decay and its clip norm, leaves the bias alone), each expert layer's
bias moves by b <- b + bias_update_speed * sign(mean load - load), from
the step's assignments to each of the E experts.

Departures from the published model, which the program shares: the rope
dims pair as rotate-half where the source interleaves (``rope_interleave``):
the same function up to a fixed permutation of the rope columns of
``wq_b`` and ``wkv_a``.  The experts not held here, and the layers cut, are
left out (the configuration file's deployment).  The depth's output goes
through the model's own final norm.

It runs in blocks so that it fits on one chip once the program's state is
freed: attention over blocks of queries, the head over blocks of
positions, each layer rematerialised under ``jax.grad``, one sequence at
a time.

(No ``from __future__ import annotations``: the harness loads this file
as a module it does not register, where a dataclass cannot resolve string
annotations.)
"""
from dataclasses import dataclass
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

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
    q_lora: int
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
    bias_rate: float
    mtp_weight: float

    @classmethod
    def from_file(cls, c: Dict) -> "RefConfig":
        """Sizes as the program runs them: a key in ``reduced`` at the
        file's value, the published one beside it."""
        published = c.get("published", {}).get("n_routed_experts",
                                               c["n_routed_experts"])
        return cls(d=c["hidden_size"], layers=c["num_hidden_layers"],
                   dense_layers=c["first_k_dense_replace"],
                   heads=c["num_attention_heads"],
                   nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"],
                   vdim=c["v_head_dim"], q_lora=c["q_lora_rank"],
                   kv_lora=c["kv_lora_rank"], vocab=c["vocab_size"],
                   rope_theta=float(c["rope_theta"]),
                   eps=float(c["rms_norm_eps"]), experts=published,
                   held=c["n_routed_experts"],
                   top_k=c["num_experts_per_tok"],
                   norm_topk=bool(c["norm_topk_prob"]),
                   routed_scale=float(c["routed_scaling_factor"]),
                   aux_alpha=float(c["aux_loss_alpha"]),
                   bias_rate=float(c["bias_update_speed"]),
                   mtp_weight=float(c["mtp_loss_weight"]))


def attention(mm, c: RefConfig, p, h):
    S, H = h.shape[0], c.heads
    ql = rmsnorm(mm("sd,dr->sr", h, p["wq_a"]["w"]), p["q_norm"]["scale"],
                 c.eps)
    q = mm("sr,rf->sf", ql, p["wq_b"]["w"]).reshape(S, H, c.nope + c.rope)
    kva = mm("sd,df->sf", h, p["wkv_a"]["w"])
    ckv = rmsnorm(kva[:, :c.kv_lora], p["kv_norm"]["scale"], c.eps)
    kr = base.rope(kva[:, None, c.kv_lora:], c.rope_theta)   # (S, 1, rope)
    kv = mm("sl,lf->sf", ckv, p["wkv_b"]["w"]).reshape(S, H, c.nope + c.vdim)
    k = jnp.concatenate([kv[..., :c.nope],
                         jnp.broadcast_to(kr, (S, H, c.rope))], -1)
    v = kv[..., c.nope:]
    q = jnp.concatenate([q[..., :c.nope],
                         base.rope(q[..., c.nope:], c.rope_theta)], -1)
    scale = (c.nope + c.rope) ** -0.5
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
    """(output, balance loss, assignments to each of the E experts) of one
    expert layer on one sequence: every held expert runs on every token,
    at zero gate where not chosen."""
    S = h.shape[0]
    s = jax.nn.sigmoid(mm("sd,de->se", h, p["router"]["w"]))   # (S, E)
    _, idx = jax.lax.top_k(s + p["router"]["bias"], c.top_k)
    vals = jnp.take_along_axis(s, idx, 1)
    if c.norm_topk:
        vals = vals / jnp.sum(vals, -1, keepdims=True)
    chosen = jax.nn.one_hot(idx, c.experts, dtype=f32)          # (S, K, E)
    gates = jnp.sum(chosen * vals[..., None], 1) * c.routed_scale
    a = jax.nn.silu(mm("sd,edf->esf", h, p["w_gate"]))
    y = mm("esf,efd->esd", a * mm("sd,edf->esf", h, p["w_up"]), p["w_down"])
    out = jnp.einsum("se,esd->sd", gates[:, :c.held], y, precision=HIGHEST)
    count = jnp.sum(chosen, (0, 1))                               # (E,)
    share = s / jnp.sum(s, -1, keepdims=True)
    aux = jnp.sum(count * jnp.mean(share, 0)) * c.experts / (S * c.top_k)
    return out + swiglu(mm, p["shared"], h), aux, count


def layer(mm, c: RefConfig, x, p):
    """(x, balance loss, loads) of one layer; a dense layer's are 0."""
    h = rmsnorm(x, p["norm1"]["scale"], c.eps)
    x = x + attention(mm, c, p["attn"], h)
    h = rmsnorm(x, p["norm2"]["scale"], c.eps)
    if "ffn_moe" in p:
        y, aux, load = moe(mm, c, p["ffn_moe"], h)
    else:
        y, aux, load = swiglu(mm, p["ffn"], h), jnp.zeros((), f32), \
            jnp.zeros((c.experts,), f32)
    return x + y, aux, load


def trunk(mm, c: RefConfig, W, tokens):
    """The last layer's output (S, d) of one sequence before the final
    norm, the sum of its expert layers' balance losses, and the expert
    layers' loads (layers - dense, E)."""
    x = jnp.take(W["embed"]["table"], tokens, axis=0).astype(f32)
    aux = jnp.zeros((), f32)
    for i in range(c.dense_layers):
        x, a, _ = jax.checkpoint(partial(layer, mm, c))(
            x, W["stack"]["prefix"][f"blk{i}"])
        aux = aux + a

    def body(x, p):
        x, a, load = layer(mm, c, x, p)
        return x, (a, load)

    x, (auxes, loads) = jax.lax.scan(jax.checkpoint(body), x,
                                     W["stack"]["periods"]["sub0"])
    return x, aux + jnp.sum(auxes), loads


@partial(jax.jit, static_argnums=(0, 1))
def logits_at(mm, c: RefConfig, W, tokens, rows):
    """Logits (len(rows), vocab) at the given positions of one sequence."""
    x = trunk(mm, c, W, tokens)[0]
    h = rmsnorm(x, W["final_norm"]["scale"], c.eps)[rows]
    return mm("sd,dv->sv", h, W["lm_head"]["w"])


@partial(jax.jit, static_argnums=(0, 1))
def gaps_at(mm, c: RefConfig, W, tokens, rows, chosen):
    lg = logits_at(mm, c, W, tokens, rows)
    return jnp.max(lg, -1) - jnp.take_along_axis(lg, chosen[:, None], 1)[:, 0]


@partial(jax.jit, static_argnums=(0, 1))
def argmax_at(mm, c: RefConfig, W, tokens, rows):
    return jnp.argmax(logits_at(mm, c, W, tokens, rows), -1).astype(jnp.int32)


def head_nll(mm, c: RefConfig, W, x, tgt):
    """Sum of the NLLs of ``tgt`` (n,) under the head at the first n rows
    of ``x`` (before the final norm), in blocks of positions."""
    h = rmsnorm(x[:tgt.shape[0]], W["final_norm"]["scale"], c.eps)
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
    return tot


def mtp(mm, c: RefConfig, W, x, tokens):
    """The depth's output rows (S, d), before the final norm, its balance
    loss and its loads."""
    p = W["mtp"]
    nxt = jnp.concatenate([tokens[1:], jnp.zeros((1,), tokens.dtype)])
    e = jnp.take(W["embed"]["table"], nxt, axis=0).astype(f32)
    hin = jnp.concatenate([rmsnorm(e, p["enorm"]["scale"], c.eps),
                           rmsnorm(x, p["hnorm"]["scale"], c.eps)], -1)
    h = mm("sf,fd->sd", hin, p["eh_proj"]["w"])
    return jax.checkpoint(partial(layer, mm, c))(h, p["block"])


def nll_sums(mm, c: RefConfig, W, tokens):
    """(sum of next-token NLLs over one sequence, sum of the depth's NLLs
    of the token after next, its balance loss over the expert layers and
    the depth, {"stack": (layers - dense, E), "mtp": (E,)} loads)."""
    x, aux, loads = trunk(mm, c, W, tokens)
    tot = head_nll(mm, c, W, x, tokens[1:])
    xm, aux_m, load_m = mtp(mm, c, W, x, tokens)
    tot_m = head_nll(mm, c, W, xm, tokens[2:])
    return tot, tot_m, aux + aux_m, {"stack": loads, "mtp": load_m}


@partial(jax.jit, static_argnums=(0, 1))
def sequence_grad(mm, c: RefConfig, W, tokens, count, count_mtp, batch):
    """Gradient of this sequence's share of the step's loss (its NLL sum
    over ``count`` targets, its depth's over ``count_mtp``, its balance
    loss over ``batch`` sequences), its NLL sum and its loads."""
    def share(w):
        tot, tot_m, aux, loads = nll_sums(mm, c, w, tokens)
        return tot / count + c.mtp_weight * tot_m / count_mtp \
            + c.aux_alpha * aux / batch, (tot, loads)
    g, (tot, loads) = jax.grad(share, has_aux=True)(W)
    return g, tot, loads


@partial(jax.jit, donate_argnums=(0, 1))
def _add(acc, g):
    return jax.tree.map(jnp.add, acc, g)


def _is_bias(path: str) -> bool:
    """A router's correction bias: no gradient, no AdamW, no decay."""
    return path.endswith("/router/bias")


@partial(jax.jit, static_argnums=(0,), donate_argnums=(1, 2, 3, 4))
def adamw(opt_items: Tuple, W, m, v, g, step, lr):
    """One AdamW step as the recipe states it (global-norm clip, bias
    correction, decay off norm scales) over every leaf but the routers'
    correction biases, which it leaves as they are; all four trees
    donated.  Returns (W, m, v, clipped g)."""
    opt = dict(opt_items)
    paths = base._paths(W)
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x, path in
                        zip(jax.tree.leaves(g), jax.tree.leaves(paths))
                        if not _is_bias(path)))
    scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(norm, 1e-9))
    g = jax.tree.map(lambda x: x * scale, g)
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    bc1, bc2 = 1.0 - b1 ** step, 1.0 - b2 ** step

    def upd(p, gi, mi, vi, path):
        if _is_bias(path):
            return p, mi, vi
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


@partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
def rebalance(rate: float, W, loads):
    """b <- b + rate * sign(mean load - load) for each expert layer's
    router (the stacked layers and the depth)."""
    W = jax.tree.map(lambda x: x, W)            # containers of its own
    for router, load in ((W["stack"]["periods"]["sub0"]["ffn_moe"]["router"],
                          loads["stack"]),
                         (W["mtp"]["block"]["ffn_moe"]["router"],
                          loads["mtp"])):
        router["bias"] = router["bias"] + rate * jnp.sign(
            jnp.mean(load, -1, keepdims=True) - load)
    return W


def train(mm, c: RefConfig, W, batches, opt: Dict, steps: int):
    """``steps`` AdamW steps, each followed by the bias rule, from the
    float32 weights ``W`` (donated) on ``batches[i]`` ((B, S) int32 each).
    Returns (mean NLL of each step, the first clipped gradient as host
    arrays, weights).  At most the weights, both moments and one gradient
    are on the device at once."""
    m = v = None
    losses, g1 = [], None
    opt_items = tuple(sorted(opt.items()))
    for i in range(steps):
        toks = batches[i]
        B, S = toks.shape
        count, count_mtp = float(B * (S - 1)), float(B * (S - 2))
        g, tot, loads = None, 0.0, None
        for r in range(B):
            gr, t, ld = sequence_grad(mm, c, W, toks[r], count, count_mtp,
                                      float(B))
            g = gr if g is None else _add(g, gr)
            loads = ld if loads is None else jax.tree.map(jnp.add, loads, ld)
            tot += float(t)
        losses.append(tot / count)
        if m is None:
            m = jax.tree.map(jnp.zeros_like, W)
            v = jax.tree.map(jnp.zeros_like, W)
        W, m, v, g = adamw(opt_items, W, m, v, g, float(i + 1),
                           base.lr_at(opt, i + 1))
        W = rebalance(c.bias_rate, W, loads)
        if i == 0:
            g1 = jax.device_get(g)
        del g
    return losses, g1, W
