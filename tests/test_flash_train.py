"""The causal flash kernel of the train step, and when ``L.attention``
chooses it.

The kernel runs in the Pallas interpreter against ``L.full_attention`` on
the same bf16 inputs, forward and gradients, latent attention's unequal
head dims and YaRN scale among them.  The dispatch is checked by lowering
``L.attention`` for a TPU (no chip needed): the kernel's custom call
appears only for causal self-attention from position 0 over the whole
sequence, at any scale; ``kv_len``, ``q_offset``, non-causal and other
long calls keep the online-softmax scan, and decode and short calls full
attention.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.config import get_arch
from repro.kernels.flash_attention.ops import causal_flash_attention_op
from repro.models import layers as L
from repro.models.attention import mla_softmax_scale
from repro.sharding import activation_rules

BF16 = jnp.bfloat16
# DeepSeek-V2's: 192^-1/2 times YaRN's mscale squared
MLA_SCALE = mla_softmax_scale(get_arch("deepseek-v2-lite").model.attention)


def _qkv(hq, hkv, seq, hd, batch=1, vd=None):
    vd = hd if vd is None else vd
    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(kq, (batch, hq, seq, hd)).astype(BF16)
    k = jax.random.normal(kk, (batch, hkv, seq, hd)).astype(BF16)
    v = jax.random.normal(kv, (batch, hkv, seq, vd)).astype(BF16)
    w = jax.random.normal(kw, (batch, hq, seq, vd))
    return q, k, v, w


def _close(got, want):
    """bf16 inputs and outputs, f32 accumulation: a few bf16 roundings of
    the largest magnitude."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, rtol=2e-2,
                               atol=2e-2 * np.abs(want).max())


# (Hq, Hkv, S, hd, batch, vd, scale): at S 2048 the 2 x 2 block grid holds
# diagonal, full and skipped blocks; ``mla`` is latent attention's q.k 192
# (128 + 64 rope) with v 128 under its YaRN scale
CASES = {"mha": (2, 2, 2048, 64, 1, 64, None),
         "gqa": (4, 2, 2048, 64, 1, 64, None),
         "batch2": (2, 1, 2048, 64, 2, 64, None),
         "hd128": (2, 2, 2048, 128, 1, 128, None),
         "mla": (2, 2, 2048, 192, 1, 128, MLA_SCALE)}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_full_attention(case):
    from repro.kernels.flash_attention.train import causal_flash_attention

    hq, hkv, seq, hd, batch, vd, scale = CASES[case]
    q, k, v, w = _qkv(hq, hkv, seq, hd, batch, vd)

    def kernel(q, k, v):
        return causal_flash_attention(q, k, v, scale=scale, interpret=True)

    def ref(q, k, v):
        return L.full_attention(q, k, v, causal=True, scale=scale)

    def loss(f):
        return lambda q, k, v: jnp.sum(f(q, k, v).astype(jnp.float32) * w)

    out = jax.jit(kernel)(q, k, v)
    assert out.shape == (batch, hq, seq, vd) and out.dtype == BF16
    _close(out, ref(q, k, v))
    grads = jax.jit(jax.grad(loss(kernel), (0, 1, 2)))(q, k, v)
    for got, want in zip(grads, jax.grad(loss(ref), (0, 1, 2))(q, k, v)):
        assert got.shape == want.shape and got.dtype == BF16
        _close(got, want)


def test_op_runs_in_the_interpreter():
    q, k, v, _ = _qkv(2, 2, 1024, 64)
    _close(causal_flash_attention_op(q, k, v, interpret=True),
           L.full_attention(q, k, v, causal=True))


# name: (q, k, v shapes as (Hq, Hkv, Sq, Sk, hd, vd), kwargs, dtype, kernel?)
DISPATCH = {
    "train": ((16, 16, 2048, 2048, 64, 64), {}, BF16, True),
    "gqa_train": ((4, 2, 2048, 2048, 64, 64), {}, BF16, True),
    "decode": ((16, 16, 1, 4096, 64, 64),
               {"causal": False, "kv_len": jnp.full((1,), 7, jnp.int32)},
               BF16, False),
    "kv_len": ((16, 16, 2048, 2048, 64, 64),
               {"kv_len": jnp.full((1,), 2000, jnp.int32)}, BF16, False),
    "q_offset": ((16, 16, 2048, 2048, 64, 64), {"q_offset": 512}, BF16,
                 False),
    "non_causal": ((16, 16, 2048, 2048, 64, 64), {"causal": False}, BF16,
                   False),
    "short": ((16, 16, 1024, 1024, 64, 64), {}, BF16, False),
    "cross_len": ((16, 16, 2048, 4096, 64, 64), {}, BF16, False),
    "scaled": ((16, 16, 2048, 2048, 64, 64), {"scale": 0.1}, BF16, True),
    "mla_head_dims": ((16, 16, 2048, 2048, 192, 128), {"scale": MLA_SCALE},
                      BF16, True),
    "mla_ragged": ((16, 16, 2304, 2304, 192, 128), {"scale": MLA_SCALE},
                   BF16, False),
    "ragged_seq": ((16, 16, 2304, 2304, 64, 64), {}, BF16, False),
    "float32": ((16, 16, 2048, 2048, 64, 64), {}, jnp.float32, False),
}


def _specs(hq, hkv, sq, sk, hd, vd, dtype):
    return (jax.ShapeDtypeStruct((1, hq, sq, hd), dtype),
            jax.ShapeDtypeStruct((1, hkv, sk, hd), dtype),
            jax.ShapeDtypeStruct((1, hkv, sk, vd), dtype))


def _lowered(specs, kw, platform):
    kw = dict(kw)
    causal = kw.pop("causal", True)

    def f(q, k, v):
        return L.attention(q, k, v, causal=causal, **kw)

    return jax.jit(f).trace(*specs).lower(
        lowering_platforms=(platform,)).as_text()


@pytest.mark.parametrize("name", sorted(DISPATCH))
def test_dispatch_by_shape_and_backend(name):
    shapes, kw, dtype, want = DISPATCH[name]
    specs = _specs(*shapes, dtype)
    tpu = _lowered(specs, kw, "tpu")
    assert ("tpu_custom_call" in tpu) == want
    sq, sk = shapes[2:4]
    if sq * sk > 1024 ** 2:
        assert ("stablehlo.while" in tpu) != want
    # off a TPU the same call lowers to the scan
    cpu = _lowered(specs, kw, "cpu")
    assert "tpu_custom_call" not in cpu


def test_dispatch_keeps_the_scan_on_a_multi_device_mesh():
    specs = _specs(*DISPATCH["train"][0], BF16)
    assert L.uses_flash_kernel(*specs, causal=True)
    mesh = jax.sharding.AbstractMesh((2, 2), ("data", "model"))
    with activation_rules(mesh):
        assert not L.uses_flash_kernel(*specs, causal=True)
