"""The chip's side of the kernels and the steps, without a chip.

Each kernel of ``repro.kernels``, the qwen1.5-0.5b decode step and its
train step are compiled at real widths for a described TPU v5e: nothing
runs, but the
chip's compiler refuses what would not lower (tiling, unsupported ops) or
not fit in its memory.  The kernel wrappers must also refuse to run off a
TPU unless asked for the Pallas interpreter.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.config import OptimizerConfig, get_arch
from repro.kernels.decode_attention.ops import (decode_attention,
                                                decode_attention_op)
from repro.kernels.flash_attention.ops import (causal_flash_attention,
                                               causal_flash_attention_op,
                                               flash_attention,
                                               flash_attention_op)
from repro.kernels.rwkv6_scan.ops import rwkv6_scan, rwkv6_scan_op
from repro.kernels.ssm_scan.ops import ssm_scan, ssm_scan_op
from repro.launch import common
from repro.launch import steps as steps_lib
from repro.models import api

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip cannot be read back without one, so
    these tests keep JAX's persistent cache off."""
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _decode_attention(s):
    # qwen1.5-0.5b decode: 16 heads of 64, 8 slots over a 32k cache
    bf = jnp.bfloat16
    return decode_attention, (_on(s, (8, 16, 64), bf),
                              _on(s, (8, 16, 32768, 64), bf),
                              _on(s, (8, 16, 32768, 64), bf),
                              _on(s, (), jnp.int32))


def _flash_attention(s):
    # qwen1.5-0.5b prefill: 16 heads of 64 over 4k tokens
    shape = (1, 16, 4096, 64)
    return flash_attention, tuple(_on(s, shape, jnp.bfloat16)
                                  for _ in range(3))


def _causal_flash_attention(s):
    # qwen1.5-0.5b train step: 16 heads of 64 over 4k tokens
    shape = (1, 16, 4096, 64)
    return causal_flash_attention, tuple(_on(s, shape, jnp.bfloat16)
                                         for _ in range(3))


def _rwkv6_scan(s):
    # rwkv6-1.6b: 32 heads of 64 (batch 4), 512-token chunks of a sequence
    n, seq, hd = 4 * 32, 512, 64
    return rwkv6_scan, tuple(_on(s, (n, seq, hd)) for _ in range(4)) + (
        _on(s, (n, hd)), _on(s, (n, hd, hd)))


def _ssm_scan(s):
    # jamba-1.5-large: d_inner 16384, d_state 16
    bz, seq, di, ds = 1, 512, 16384, 16
    return ssm_scan, (_on(s, (bz, seq, di)), _on(s, (bz, seq, di)),
                      _on(s, (di, ds)), _on(s, (bz, seq, ds)),
                      _on(s, (bz, seq, ds)), _on(s, (di,)),
                      _on(s, (bz, di, ds)))


KERNELS = {"causal_flash_attention": _causal_flash_attention,
           "decode_attention": _decode_attention,
           "flash_attention": _flash_attention,
           "rwkv6_scan": _rwkv6_scan,
           "ssm_scan": _ssm_scan}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, args = KERNELS[name](one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _place(sharding, tree):
    return jax.tree.map(lambda s: _on(sharding, s.shape, s.dtype), tree)


def _total_bytes(compiled):
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


def test_qwen_decode_step_fits_one_v5e(one_chip):
    cfg = common.run_config(get_arch("qwen1.5-0.5b"), smoke=False)
    assert cfg.param_dtype == "bfloat16"

    slots = 8
    params = _place(one_chip, api.param_shapes(cfg))
    state = _place(one_chip, api.init_decode_state(cfg, slots, 2048))
    tokens = _on(one_chip, (slots,), jnp.int32)
    compiled = jax.jit(steps_lib.make_serve_step(cfg),
                       donate_argnums=(1,)).lower(
        params, state, tokens, tokens).compile()
    assert 0 < _total_bytes(compiled) < V5E_HBM_BYTES


def test_qwen_train_step_runs_the_flash_kernel_on_one_v5e(one_chip):
    """The benchmark's train step (1 x 4096 tokens, f32 master weights, bf16
    compute, remat dots): attention runs the splash kernels under the
    ``attn_core`` scope, no scan loop is left there, and the step fits."""
    cfg = common.run_config(get_arch("qwen1.5-0.5b"), smoke=False)
    cfg = dataclasses.replace(cfg, param_dtype="float32",
                              compute_dtype="bfloat16")
    opt_cfg = OptimizerConfig()
    params, opt_state = (_place(one_chip, t) for t in
                         steps_lib.train_state_shapes(cfg, opt_cfg))
    batch = {"tokens": _on(one_chip, (1, 4096), jnp.int32)}
    compiled = jax.jit(steps_lib.make_train_step(cfg, opt_cfg, remat="dots"),
                       donate_argnums=(0, 1)).lower(
        params, opt_state, batch).compile()
    text = compiled.as_text()
    # a custom call's attributes span lines; its own metadata comes first
    calls = re.findall(r"%(splash_mha_\w+?)\.\d+ = .*?custom-call\(.*?"
                       r'metadata=\{op_name="([^"]*)"', text, re.S)
    kinds = {name for name, _ in calls}
    assert kinds == {"splash_mha_fwd_residuals", "splash_mha_dkv_no_residuals"}
    assert all("/attn_core/" in op for _, op in calls)
    loops = re.findall(r' while\(.*?op_name="([^"]*)"', text)
    assert loops and not any("attn_core" in op for op in loops)
    assert 0 < _total_bytes(compiled) < 0.9 * V5E_HBM_BYTES


OPS = {"causal_flash_attention": (causal_flash_attention_op, 3),
       "decode_attention": (decode_attention_op, 4),
       "flash_attention": (flash_attention_op, 3),
       "rwkv6_scan": (rwkv6_scan_op, 6),
       "ssm_scan": (ssm_scan_op, 7)}


@pytest.mark.parametrize("name", sorted(OPS))
def test_kernel_op_refuses_cpu_without_interpret(name):
    assert jax.default_backend() == "cpu"
    op, n_args = OPS[name]
    with pytest.raises(RuntimeError, match="interpret=True"):
        op(*[None] * n_args)
