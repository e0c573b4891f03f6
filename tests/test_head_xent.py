"""The vocabulary head and loss (``lm.head_xent``) against the plain loss.

``head_xent`` never holds (rows, V) logits: the forward scans row chunks
for the log-sum-exp and the backward recomputes the logits by vocabulary
chunks under a ``jax.custom_vjp``.  Its value and its gradients, for the
hidden states and for the head's weights (the tied table or ``lm_head``),
are held to ``jax.grad`` of a full-logits float32 log-softmax
cross-entropy, with more rows than one chunk so that the chunks are
exercised, including a last vocabulary chunk that overlaps the one before.
The token-chunk loss that a mesh of more than one device runs
(``lm.chunked_xent``) is held to the same plain loss.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.config import get_arch
from repro.models import lm

D = 32


def _plain_xent(w, h, targets, mask, tied, softcap):
    z = jnp.einsum("bsd,vd->bsv" if tied else "bsd,dv->bsv", h, w)
    if softcap:
        z = jnp.tanh(z / softcap) * softcap
    nll = -jnp.take_along_axis(jax.nn.log_softmax(z, axis=-1),
                               targets[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


@pytest.mark.parametrize("loss,tied,masked,vocab,softcap,batch", [
    ("head_xent", True, False, 1024, 0.0, 1),
    ("head_xent", False, False, 1024, 0.0, 1),
    ("head_xent", True, True, 1000, 0.0, 1),
    ("head_xent", False, True, 1000, 0.0, 1),
    ("head_xent", True, False, 1000, 30.0, 1),
    ("head_xent", False, True, 1000, 30.0, 2),
    ("head_xent", True, True, 1000, 0.0, 2),
    ("chunked_xent", True, True, 1000, 0.0, 2),
    ("chunked_xent", False, False, 1000, 30.0, 1),
], ids=lambda v: str(v))
def test_head_xent_matches_full_logits_loss_and_gradients(
        loss, tied, masked, vocab, softcap, batch):
    seq = 1300 // batch
    rows = batch * seq
    vc = lm.xent_vocab_chunk(rows, vocab)
    assert vc < vocab and (vocab % vc != 0) == (vocab == 1000)
    cfg = dataclasses.replace(get_arch("qwen1.5-0.5b").smoke,
                              vocab_size=vocab, d_model=D, tie_embeddings=tied,
                              logit_softcap=softcap, param_dtype="float32",
                              compute_dtype="float32")
    k = jax.random.split(jax.random.PRNGKey(vocab + batch), 4)
    w = jax.random.normal(k[0], (vocab, D) if tied else (D, vocab)) * 0.5
    h = jax.random.normal(k[1], (batch, seq, D))
    targets = jax.random.randint(k[2], (batch, seq), 0, vocab)
    mask = (jax.random.uniform(k[3], (batch, seq)) < 0.7).astype(jnp.float32) \
        if masked else None

    def program(w, h):
        p = {"embed": {"table": w}} if tied else {"lm_head": {"w": w}}
        return getattr(lm, loss)(p, cfg, h, targets, mask)

    def plain(w, h):
        return _plain_xent(w, h, targets, jnp.ones_like(h[..., 0])
                           if mask is None else mask, tied, softcap)

    with jax.default_matmul_precision("float32"):
        value, (dw, dh) = jax.value_and_grad(program, argnums=(0, 1))(w, h)
        ref, (rdw, rdh) = jax.value_and_grad(plain, argnums=(0, 1))(w, h)
    np.testing.assert_allclose(value, ref, rtol=1e-5)
    for got, want in ((dw, rdw), (dh, rdh)):
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=1e-5 * float(jnp.max(jnp.abs(want))))
