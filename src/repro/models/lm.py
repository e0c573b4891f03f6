"""Decoder-only language model (dense / MoE / SSM / RWKV / hybrid / VLM).

Public surface (used by repro.models.api):
  init_params, forward, loss_fn, init_decode_state, prefill, decode_step
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import ModelConfig
from repro.models import blocks as B
from repro.models import layers as L
from repro.models import scopes
from repro.sharding import constrain

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
    return p


@jax.named_scope(scopes.HEAD)
def _head(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    cd = L.dtype_of(cfg.compute_dtype)
    if cfg.tie_embeddings:
        logits = L.logits_from_embedding(p["embed"], x, cfg.logit_softcap, cd)
    else:
        logits = jnp.einsum("...d,dv->...v", x.astype(cd),
                            p["lm_head"]["w"].astype(cd),
                            preferred_element_type=jnp.float32)
        if cfg.logit_softcap:
            logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
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
    x, _, aux = B.apply_stack(p["stack"], x, cfg, mode="train", remat=remat)
    x = L.apply_norm(p["final_norm"], x, cfg.norm_eps)
    return _head(p, x, cfg), aux


def hidden_states(p: Params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
                  *, remat: str = "dots") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Final-norm hidden states (pre-head). Returns (h, aux)."""
    x = _embed_inputs(p, cfg, batch)
    x, _, aux = B.apply_stack(p["stack"], x, cfg, mode="train", remat=remat)
    return L.apply_norm(p["final_norm"], x, cfg.norm_eps), aux


def chunked_xent(p: Params, cfg: ModelConfig, h: jnp.ndarray,
                 targets: jnp.ndarray, mask: Optional[jnp.ndarray] = None,
                 chunk: int = 512) -> jnp.ndarray:
    """Cross-entropy without materialising full (B,S,V) logits: scan over
    sequence chunks, computing head projection + log-softmax per chunk."""
    Bz, S, D = h.shape
    chunk = min(chunk, S)
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


def loss_fn(p: Params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray], *,
            remat: str = "dots") -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Next-token cross-entropy (+ MoE aux); chunked head+xent keeps the
    (B,S,V) logits tensor out of memory."""
    h, aux = hidden_states(p, cfg, batch, remat=remat)
    n_prefix = h.shape[1] - batch["tokens"].shape[1]
    if n_prefix > 0:
        h = h[:, n_prefix:]
    targets = batch["tokens"][:, 1:]
    mask = batch.get("loss_mask")
    loss = chunked_xent(p, cfg, h[:, :-1], targets,
                        None if mask is None else mask[:, 1:])
    aux_coef = cfg.moe.aux_loss_coef if cfg.moe else 0.0
    total = loss + aux_coef * aux
    return total, {"loss": loss, "aux": aux, "total": total}


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
