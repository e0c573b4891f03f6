"""Decoder-only language model (dense / MoE / SSM / RWKV / hybrid / VLM).

Public surface (used by repro.models.api):
  init_params, forward, loss_fn, init_decode_state, prefill, decode_step
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import ModelConfig
from repro.models import blocks as B
from repro.models import layers as L
from repro.models import scopes
from repro.sharding import active_mesh, constrain

Params = Dict[str, Any]


def init_params(key, cfg: ModelConfig) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    dt = L.dtype_of(cfg.param_dtype)
    p: Params = {
        "embed": L.init_embedding(k1, cfg.vocab_size, cfg.d_model, dt),
        "stack": B.init_stack(k2, cfg),
        "final_norm": L.init_norm(cfg.d_model, cfg.norm, dt),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = L.init_linear(k3, cfg.d_model, cfg.vocab_size, dt)
    if cfg.mtp_depth:
        p["mtp"] = init_mtp(jax.random.fold_in(key, 3), cfg)
    return p


def init_mtp(key, cfg: ModelConfig) -> Params:
    """The multi-token-prediction depth (DeepSeek-V3, one depth): norms of
    the embedding and of the hidden state, their projection, and one
    decoder block with an expert FFN; the embedding, final norm and head
    are the model's own."""
    if cfg.mtp_depth != 1:
        raise ValueError(f"mtp_depth {cfg.mtp_depth}: one depth or none")
    dt = L.dtype_of(cfg.param_dtype)
    k1, k2 = jax.random.split(key)
    d = cfg.d_model
    return {"enorm": L.init_norm(d, cfg.norm, dt),
            "hnorm": L.init_norm(d, cfg.norm, dt),
            "eh_proj": L.init_linear(k1, 2 * d, d, dt),
            "block": B.init_block(k2, cfg, "attn", "moe")}


def _head_weight(p: Params, cfg: ModelConfig) -> jnp.ndarray:
    """The head's weights as stored: the tied (V, D) table or (D, V)."""
    return p["embed"]["table"] if cfg.tie_embeddings else p["lm_head"]["w"]


def _logits(x, w, tied: bool, softcap: float) -> jnp.ndarray:
    """f32 logits of rows ``x`` against head weights ``w`` ((V, D) when
    tied, else (D, V)), both in the compute dtype; softcapped where set."""
    z = jnp.einsum("...d,vd->...v" if tied else "...d,dv->...v", x, w,
                   preferred_element_type=jnp.float32)
    return jnp.tanh(z / softcap) * softcap if softcap else z


@jax.named_scope(scopes.HEAD)
def _head(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    cd = L.dtype_of(cfg.compute_dtype)
    logits = _logits(x.astype(cd), _head_weight(p, cfg).astype(cd),
                     cfg.tie_embeddings, cfg.logit_softcap)
    return constrain(logits, ("batch", "seq", "vocab"))


def _embed_inputs(p: Params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
                  ) -> jnp.ndarray:
    cd = L.dtype_of(cfg.compute_dtype)
    x = L.embed(p["embed"], batch["tokens"], cd)
    if cfg.frontend and cfg.frontend.kind != "none" and "prefix_embeds" in batch:
        # modality frontend STUB: precomputed patch/frame embeddings
        pre = batch["prefix_embeds"].astype(cd)
        x = jnp.concatenate([pre, x], axis=1)
    return constrain(x, ("batch", "seq", "embed"))


def forward(p: Params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray], *,
            mode: str = "train", remat: str = "dots",
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward. Returns (logits, aux_loss)."""
    x = _embed_inputs(p, cfg, batch)
    x, _, stats = B.apply_stack(p["stack"], x, cfg, mode="train", remat=remat)
    x = L.apply_norm(p["final_norm"], x, cfg.norm_eps)
    return _head(p, x, cfg), stats["aux"]


def trunk(p: Params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
          *, remat: str = "dots") -> Tuple[jnp.ndarray, Dict]:
    """The last block's output, before the final norm. Returns (x,
    ``L.moe_stats`` merged over the layers)."""
    x = _embed_inputs(p, cfg, batch)
    x, _, stats = B.apply_stack(p["stack"], x, cfg, mode="train", remat=remat)
    return x, stats


XENT_ROWS = 512   # rows of a forward chunk of the head-plus-loss
LANES = 128       # a backward vocabulary chunk is a multiple of this


def xent_vocab_chunk(rows: int, vocab: int) -> int:
    """Columns of one backward vocabulary chunk of ``head_xent``: a
    multiple of ``LANES`` (or the whole vocabulary) for n to 2n chunks,
    where n is the forward's count of row chunks, so an f32 (rows, chunk)
    block is at most about the (XENT_ROWS, vocab) block of a forward chunk;
    of those widths, the one whose chunks cover the vocabulary with the
    fewest columns to spare, the widest of equals."""
    n = max(1, -(-rows // XENT_ROWS))
    widths = {min(vocab, -(-vocab // (k * LANES)) * LANES)
              for k in range(n, 2 * n + 1)}
    return min(widths, key=lambda w: (-(-vocab // w) * w, -w))


@jax.named_scope(scopes.HEAD)
def _head_xent_fwd(spec, w, h, targets, mask):
    tied, softcap, cd = spec
    wc, hc = w.astype(cd), h.astype(cd)
    n = h.shape[0]
    rows = min(XENT_ROWS, n)
    nc = -(-n // rows)
    hb = jnp.pad(hc, ((0, nc * rows - n), (0, 0))).reshape(nc, rows, -1)

    def step(_, hr):
        z = _logits(hr, wc, tied, softcap)
        m = jnp.max(z, axis=-1)
        return None, m + jnp.log(jnp.sum(jnp.exp(z - m[:, None]), axis=-1))

    lse = jax.lax.scan(step, None, hb)[1].reshape(-1)[:n]
    wt = jnp.take(wc, targets, axis=0) if tied else \
        jnp.take(wc, targets, axis=1).T
    zt = jnp.einsum("nd,nd->n", hc, wt, preferred_element_type=jnp.float32)
    if softcap:
        zt = jnp.tanh(zt / softcap) * softcap
    count = jnp.maximum(jnp.sum(mask), 1.0)
    loss = jnp.sum(mask * (lse - zt)) / count
    return loss, (w, h, targets, mask, lse, count)


@jax.named_scope(scopes.HEAD)
def _head_xent_bwd(spec, res, g):
    tied, softcap, cd = spec
    w, h, targets, mask, lse, count = res
    wc, hc = w.astype(cd), h.astype(cd)
    vocab = w.shape[0] if tied else w.shape[1]
    vc = xent_vocab_chunk(h.shape[0], vocab)
    scale = (g * mask / count)[:, None]

    # Chunk k covers columns [k*vc, (k+1)*vc); the last starts at vocab - vc
    # instead, and zeroes the columns an earlier chunk owns.  Chunks run
    # last first, so that earlier chunk then rewrites those rows of dw.
    def step(carry, k):
        dh, dw = carry
        start = jnp.minimum(k * vc, vocab - vc)
        at = (start, 0) if tied else (0, start)
        wk = jax.lax.dynamic_slice(wc, at, (vc, w.shape[1]) if tied
                                   else (w.shape[0], vc))
        z = _logits(hc, wk, tied, softcap)
        cols = start + jnp.arange(vc)
        d = (jnp.exp(z - lse[:, None])
             - (cols == targets[:, None]).astype(jnp.float32)) * scale
        if softcap:
            d = d * (1.0 - jnp.square(z / softcap))
        d = jnp.where(cols >= k * vc, d, 0.0).astype(cd)
        dh = dh + jnp.einsum("nv,vd->nd" if tied else "nv,dv->nd", d, wk,
                             preferred_element_type=jnp.float32)
        dwk = jnp.einsum("nv,nd->vd" if tied else "nv,nd->dv", d, hc,
                         preferred_element_type=jnp.float32)
        return (dh, jax.lax.dynamic_update_slice(dw, dwk.astype(w.dtype),
                                                 at)), None

    nc = -(-vocab // vc)
    (dh, dw), _ = jax.lax.scan(
        step, (jnp.zeros(h.shape, jnp.float32), jnp.zeros_like(w)),
        jnp.arange(nc), reverse=True)
    return dw, dh.astype(h.dtype), None, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _head_xent(spec, w, h, targets, mask):
    return _head_xent_fwd(spec, w, h, targets, mask)[0]


_head_xent.defvjp(_head_xent_fwd, _head_xent_bwd)


def head_xent(p: Params, cfg: ModelConfig, h: jnp.ndarray,
              targets: jnp.ndarray, mask: Optional[jnp.ndarray] = None,
              ) -> jnp.ndarray:
    """Mean next-token cross-entropy of the vocabulary head over the rows
    of ``h`` (B, S, D) whose ``mask`` is set, never holding (rows, V)
    logits.  The forward scans ``XENT_ROWS``-row chunks for each row's f32
    log-sum-exp and gathers the target's logit; the backward recomputes
    the logits by vocabulary chunks (``xent_vocab_chunk``), so the input's
    gradient sums in an f32 carry and each row of the head's weight
    gradient is written once.  Products take compute-dtype operands with
    f32 accumulation.  On a mesh of more than one device, where the
    vocabulary is sharded, ``chunked_xent`` runs instead."""
    mesh = active_mesh()
    if mesh is not None and mesh.size > 1:
        return chunked_xent(p, cfg, h, targets, mask)
    D = h.shape[-1]
    mf = jnp.ones(targets.shape, jnp.float32) if mask is None else \
        mask.astype(jnp.float32)
    spec = (cfg.tie_embeddings, float(cfg.logit_softcap or 0.0),
            L.dtype_of(cfg.compute_dtype))
    return _head_xent(spec, _head_weight(p, cfg), h.reshape(-1, D),
                      targets.reshape(-1), mf.reshape(-1))


def chunked_xent(p: Params, cfg: ModelConfig, h: jnp.ndarray,
                 targets: jnp.ndarray, mask: Optional[jnp.ndarray] = None,
                 ) -> jnp.ndarray:
    """Cross-entropy without materialising full (B,S,V) logits: scan over
    sequence chunks, computing head projection + log-softmax per chunk.
    Autodiff carries the head's weight gradient through the scan, so it
    serves only a sharded vocabulary (``head_xent``)."""
    Bz, S, D = h.shape
    chunk = min(XENT_ROWS, S)
    nc = -(-S // chunk)
    pad = nc * chunk - S
    hf = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
    tf = jnp.pad(targets, ((0, 0), (0, pad)))
    mf = jnp.ones((Bz, S), jnp.float32) if mask is None else \
        mask.astype(jnp.float32)
    mf = jnp.pad(mf, ((0, 0), (0, pad)))
    hf = hf.reshape(Bz, nc, chunk, D).transpose(1, 0, 2, 3)
    tf = tf.reshape(Bz, nc, chunk).transpose(1, 0, 2)
    mf = mf.reshape(Bz, nc, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    @jax.named_scope(scopes.HEAD)
    def step(carry, inp):
        hc, tc, mc = inp
        logits = _head(p, hc, cfg).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, tc[..., None], axis=-1)[..., 0]
        return (carry[0] + jnp.sum(nll * mc), carry[1] + jnp.sum(mc)), None

    (tot, cnt), _ = jax.lax.scan(
        step, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hf, tf, mf))
    return tot / jnp.maximum(cnt, 1.0)


def mtp_loss(p: Params, cfg: ModelConfig, x: jnp.ndarray,
             tokens: jnp.ndarray, mask: Optional[jnp.ndarray] = None, *,
             remat: str = "dots") -> Tuple[jnp.ndarray, Dict]:
    """The multi-token-prediction depth's loss (DeepSeek-V3 section 2.2,
    one depth) from the last block's output ``x`` (B, S, D), before the
    final norm: position i takes W_eh [rmsnorm(Emb(t_{i+1}));
    rmsnorm(x_i)] through one decoder block, then the model's final norm
    and head, and predicts t_{i+2}.  The depth runs over all S positions,
    the last one pairing x with token 0 (it has no next token); causal
    attention keeps it out of every other position, and no loss reads it.
    Returns (mean cross-entropy over the S - 2 targets, the block's
    ``L.moe_stats``)."""
    m = p["mtp"]
    cd, eps = L.dtype_of(cfg.compute_dtype), cfg.norm_eps
    with jax.named_scope(scopes.MTP):
        nxt = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
        e = L.apply_norm(m["enorm"], L.embed(p["embed"], nxt, cd), eps)
        h = L.linear(m["eh_proj"], jnp.concatenate(
            [e, L.apply_norm(m["hnorm"], x, eps)], axis=-1), cd)
        block = B._remat_wrap(lambda blk, h: B.apply_block(
            blk, h, cfg, "attn", "moe", mode="train"), remat)
        h, _, stats = block(m["block"], h)
        h = L.apply_norm(p["final_norm"], h, eps)
        loss = head_xent(p, cfg, h[:, :-2], tokens[:, 2:],
                         None if mask is None else mask[:, 2:])
    return loss, stats


def loss_fn(p: Params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray], *,
            remat: str = "dots") -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Next-token cross-entropy (+ MoE aux, + the multi-token-prediction
    depth's loss where the model has one, ``mtp_loss``); ``head_xent``
    keeps the (B,S,V) logits tensor out of memory.  A MoE model's metrics
    also count the assignments routed to the experts held
    (``moe_held_assignments``, summed over the expert layers) and the worst
    layer's largest held expert's load over its mean (``moe_max_load``).
    Sigmoid routers add the worst layer's largest load over the mean of
    all E experts (``moe_max_load_all``) and ``router_loads``, the loads
    ``L.rebalance_router_bias`` takes, at the places of the biases in
    ``p``."""
    x, stats = trunk(p, cfg, batch, remat=remat)
    n_prefix = x.shape[1] - batch["tokens"].shape[1]
    if n_prefix > 0:
        x = x[:, n_prefix:]
    h = L.apply_norm(p["final_norm"], x, cfg.norm_eps)
    targets = batch["tokens"][:, 1:]
    mask = batch.get("loss_mask")
    loss = head_xent(p, cfg, h[:, :-1], targets,
                     None if mask is None else mask[:, 1:])
    metrics = {"loss": loss}
    loads = {"stack": stats["loads"]} if "loads" in stats else {}
    total = loss
    if cfg.mtp_depth:
        extra, mtp_stats = mtp_loss(p, cfg, x, batch["tokens"], mask,
                                    remat=remat)
        total = total + cfg.mtp_loss_coef * extra
        stats = L.merge_moe_stats(stats, mtp_stats)
        if "loads" in mtp_stats:
            loads["mtp"] = {"block": mtp_stats["loads"]}
        metrics["mtp_loss"] = extra
    aux = stats["aux"]
    aux_coef = cfg.moe.aux_loss_coef if cfg.moe else 0.0
    total = total + aux_coef * aux
    metrics.update(aux=aux, total=total)
    if cfg.moe:
        metrics.update(moe_held_assignments=stats["held"],
                       moe_max_load=stats["max_load"])
    if loads:
        metrics["moe_max_load_all"] = jnp.max(jnp.stack([
            jnp.max(jnp.max(v, -1) / jnp.mean(v, -1))
            for v in jax.tree.leaves(loads)]))
        metrics["router_loads"] = loads
    return total, metrics


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------


def init_decode_state(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    """ShapeDtypeStruct pytree for the decode cache (allocate with zeros)."""
    return B.stack_cache_spec(cfg, batch, max_len)


def allocate_decode_state(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    spec = init_decode_state(cfg, batch, max_len)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)


def prefill(p: Params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
            ) -> Tuple[jnp.ndarray, Params]:
    """Process the full prompt; returns (last-position logits, cache).

    The returned attention caches hold exactly the prompt (S positions);
    callers growing beyond S must allocate larger caches up front by padding
    the prompt (standard bucket serving).
    """
    x = _embed_inputs(p, cfg, batch)
    x, cache, _ = B.apply_stack(p["stack"], x, cfg, mode="prefill",
                                remat="none")
    x = L.apply_norm(p["final_norm"], x, cfg.norm_eps)
    logits = _head(p, x[:, -1:], cfg)
    return logits, cache


def decode_step(p: Params, cfg: ModelConfig, state: Params,
                tokens: jnp.ndarray, pos: jnp.ndarray,
                ) -> Tuple[jnp.ndarray, Params]:
    """One decode step.  tokens: (B,) int32; pos: scalar or per-slot (B,)
    int32 (cache write index; attention attends to [0, pos], per slot when
    a vector — continuous batching).  Returns (logits (B,V), state)."""
    cd = L.dtype_of(cfg.compute_dtype)
    x = L.embed(p["embed"], tokens[:, None], cd)
    x = constrain(x, ("batch", None, "embed"))
    x, new_cache, _ = B.apply_stack(p["stack"], x, cfg, mode="decode",
                                    cache=state, pos=pos, remat="none")
    x = L.apply_norm(p["final_norm"], x, cfg.norm_eps)
    logits = _head(p, x, cfg)[:, 0]
    return logits, new_cache
