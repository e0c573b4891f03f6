"""The chip's side of the kernels and the steps, without a chip.

Each kernel of ``repro.kernels``, the qwen1.5-0.5b decode step and its
train step, and the deepseek-v2-lite cell's train step are compiled at
real widths for a described TPU v5e: nothing
runs, but the
chip's compiler refuses what would not lower (tiling, unsupported ops) or
not fit in its memory.  The kernel wrappers must also refuse to run off a
TPU unless asked for the Pallas interpreter.
"""
import dataclasses
import math
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
from repro.models import layers as L
from repro.sharding import activation_rules

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


def _grouped_swiglu(s):
    # granite-moe-1b-a400m's experts (32 of 512 over d 1024), 4096 tokens x
    # 8 choices, forward and backward: the tiles swept at deepseek-v2-lite's
    # widths clamp to these
    e, d, f, rows, bf = 32, 1024, 512, 4096 * 8, jnp.bfloat16

    def fwd_bwd(x, w_gate, w_up, w_down, sizes):
        def total(*a):
            p = dict(zip(("w_gate", "w_up", "w_down"), a[1:]))
            return jnp.sum(L._grouped_swiglu(p, a[0], sizes, bf))
        return jax.grad(total, argnums=(0, 1, 2, 3))(x, w_gate, w_up, w_down)
    return fwd_bwd, (_on(s, (rows, d), bf), _on(s, (e, d, f), bf),
                     _on(s, (e, d, f), bf), _on(s, (e, f, d), bf),
                     _on(s, (e,), jnp.int32))


KERNELS = {"causal_flash_attention": _causal_flash_attention,
           "decode_attention": _decode_attention,
           "flash_attention": _flash_attention,
           "grouped_swiglu": _grouped_swiglu,
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


def _train_step(one_chip, cfg, opt_cfg, batch_shape, remat):
    """The jitted train step traced for one described v5e."""
    params, opt_state = (_place(one_chip, t) for t in
                         steps_lib.train_state_shapes(cfg, opt_cfg))
    batch = {"tokens": _on(one_chip, batch_shape, jnp.int32)}
    return jax.jit(steps_lib.make_train_step(cfg, opt_cfg, remat=remat),
                   donate_argnums=(0, 1)).trace(params, opt_state, batch)


@pytest.fixture(scope="module")
def qwen_train(one_chip):
    """(config, compiled) of the benchmark's qwen train step: 1 x 4096
    tokens, f32 master weights, bf16 compute, remat dots."""
    cfg = common.run_config(get_arch("qwen1.5-0.5b"), smoke=False)
    cfg = dataclasses.replace(cfg, param_dtype="float32",
                              compute_dtype="bfloat16")
    return cfg, _train_step(one_chip, cfg, OptimizerConfig(), (1, 4096),
                            "dots").lower().compile()


def test_qwen_train_step_runs_the_flash_kernel_on_one_v5e(qwen_train):
    """The benchmark's train step (1 x 4096 tokens, f32 master weights, bf16
    compute, remat dots): attention runs the splash kernels under the
    ``attn_core`` scope, no scan loop is left there, the head's loops write
    each row of its weight gradient once, and the step fits."""
    cfg, compiled = qwen_train
    text = compiled.as_text()
    _assert_splash_attention(text)
    _assert_head_writes_weight_rows_once(text, f"{cfg.vocab_size},"
                                         f"{cfg.d_model}")
    assert 0 < _total_bytes(compiled) < 0.9 * V5E_HBM_BYTES


def _assert_head_writes_weight_rows_once(text, dims):
    """The head-plus-loss runs as loops under ``head``, every op in them
    that has a name names ``head`` (a constant's name is any of its
    users'), and the f32 weight gradient (``dims``,
    as the weights are stored) that the backward loop carries is only ever
    written a chunk at a time by dynamic-update-slice: no loop adds into it
    (what autodiff of a token-chunk scan did, once a chunk)."""
    comps = dict(re.findall(r"^(?:ENTRY )?%(\S+) [^\n]*\{\n(.*?)\n\}", text,
                            re.S | re.M))
    head = re.compile(r'op_name="[^"]*\bhead\b')
    loops = [body for body in re.findall(r" while\(.*?body=%(\S+?),", text)
             if head.search(comps[body])]
    assert len(loops) == 2, "a forward and a backward loop under head"
    grad = f"f32[{dims}]"
    writes = []
    for body in loops:
        for line in comps[body].splitlines():
            op = re.search(r'op_name="([^"]*)"', line)
            assert op is None or " constant(" in line \
                or re.search(r"\bhead\b", op.group(1)), line
            m = re.match(r"\s*(?:ROOT )?%\S+ = (\S+?)\{[^}]*\} ([\w-]+)\(",
                         line)
            if m and m.group(1) == grad:
                assert m.group(2) in ("get-tuple-element",
                                      "dynamic-update-slice"), line
                writes.append(m.group(2))
    assert writes.count("dynamic-update-slice") == 1


def _assert_splash_attention(text):
    """Attention in the compiled step runs the splash forward and fused
    backward under ``attn_core``, and no scan loop is left there."""
    # a custom call's attributes span lines; its own metadata comes first
    calls = re.findall(r"%(splash_mha_\w+?)\.\d+ = .*?custom-call\(.*?"
                       r'metadata=\{op_name="([^"]*)"', text, re.S)
    kinds = {name for name, _ in calls}
    assert kinds == {"splash_mha_fwd_residuals", "splash_mha_dkv_no_residuals"}
    assert all("/attn_core/" in op for _, op in calls)
    loops = re.findall(r' while\(.*?op_name="([^"]*)"', text)
    assert loops and not any("attn_core" in op for op in loops)


def _cell(config, mix):
    """The ModelConfig and mix of a benchmark cell, through the benchmark's
    own cut (``benchmarks/chip/harness.py``)."""
    import json
    import sys
    from pathlib import Path

    chip = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
    sys.path.insert(0, str(chip))
    import harness

    conf = json.loads((chip / "configs" / f"{config}.json").read_text())
    mix = json.loads((chip / "traffic" / f"{mix}.json").read_text())
    return harness.program_config(conf, mix), mix


def _cell_step(one_chip, config, mix):
    """(config, mix, traced) of a cell's train step at its own sizes."""
    cfg, mix = _cell(config, mix)
    traced = _train_step(one_chip, cfg, OptimizerConfig(**mix["optimizer"]),
                         (mix["batch"], mix["seq_len"]), mix["remat"])
    return cfg, mix, traced


@pytest.fixture(scope="module")
def deepseek_train(one_chip):
    cfg, mix, traced = _cell_step(one_chip, "deepseek-v2-lite", "train_8k")
    return cfg, mix, traced.lower().compile()


def test_deepseek_v2_lite_train_step_fits_one_v5e_and_groups_by_expert(
        deepseek_train):
    """The cell's train step (1 x 8192 tokens, f32 master weights, bf16
    compute, remat dots) fits 90% of HBM by the compiler's own peak, runs
    latent attention (q.k 192, v 128) in the splash kernels under
    ``attn_core`` with no scan loop there, runs the held experts' products
    in the megablox kernels (``gmm``, and ``tgmm`` for the weights'
    gradient) under ``moe_experts``, and holds no (group, seq, expert,
    capacity) one-hot dispatch: no array in the program is near the
    S x E x S*K elements such a tensor has."""
    cfg, mix, compiled = deepseek_train
    assert cfg.moe.capacity_factor <= 0 and cfg.moe.held == 8
    S = mix["seq_len"]
    assert 0 < compiled.memory_analysis().peak_memory_in_bytes \
        < 0.9 * V5E_HBM_BYTES
    text = compiled.as_text()
    _assert_splash_attention(text)
    _assert_head_writes_weight_rows_once(text, f"{cfg.d_model},"
                                         f"{cfg.vocab_size}")
    _assert_grouped_matmuls(text)
    sizes = [math.prod(int(d) for d in dims.split(","))
             for dims in re.findall(r"[a-z0-9]+\[([0-9,]+)\]", text)]
    one_hot = S * cfg.moe.num_experts * S * cfg.moe.num_experts_per_tok
    assert max(sizes) < one_hot / 100


def _assert_grouped_matmuls(text):
    """The held experts' products run the megablox kernels (``gmm``, and
    ``tgmm`` for the weights' gradient) under ``moe_experts``; returns
    their op names."""
    calls = re.findall(r"%(t?gmm)(?:\.\d+)? = \S+ custom-call\(.*?"
                       r'metadata=\{op_name="([^"]*)"', text, re.S)
    assert {name for name, _ in calls} == {"gmm", "tgmm"}
    assert all(_named(op, "moe_experts") for _, op in calls)
    return [op for _, op in calls]


def _named(op_name: str, scope: str) -> bool:
    return scope in re.findall(r"\w+", op_name)


def _eqns(jaxpr, outer=""):
    """(full name stack, function names of the user frames) of every
    equation of a jaxpr and of the jaxprs inside it, but those inside a
    ``jit`` equation: a jitted helper is traced once for every call site
    of the same shapes, so its inner equations carry the frames of
    whichever call traced it first (the ``jit`` equation itself carries
    its own call's)."""
    from jax._src import source_info_util

    for eqn in jaxpr.eqns:
        stack = outer + "/" + str(eqn.source_info.name_stack)
        frames = {f.function_name for f in source_info_util.user_frames(
            eqn.source_info.traceback)}
        yield stack, frames
        if eqn.primitive.name != "jit":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _eqns(sub, stack)


# What XLA schedules to move data, or what only names a value: it takes
# the name of wherever XLA puts it, not of the program's scope.
_PLUMBING = ("parameter", "constant", "get-tuple-element", "tuple",
             "bitcast", "copy", "copy-start", "copy-done", "slice-start",
             "slice-done")


def _computations(text):
    return dict(re.findall(r"^(?:ENTRY )?%(\S+) [^\n]*\{\n(.*?)\n\}", text,
                           re.S | re.M))


def _holds_rows(dims: str, n: int) -> bool:
    """Whether an array of these dims holds n rows of more than one value
    (leading dims that multiply to n, then more than one value)."""
    d = [int(x) for x in dims.split(",") if x]
    return any(math.prod(d[:i]) == n and math.prod(d[i:]) > 1
               for i in range(1, len(d)))


def _assert_held_rows_bounded(text, n: int):
    """The expert layers' conditionals (the all-rows branch first, then
    the branch over the held-row bound; a forward and a backward one for
    the scanned layers and for the depth's): every op of both that the
    program names names ``moe_route`` or ``moe_experts``, and no array of
    the bounded branch holds n = S*K rows."""
    comps = _computations(text)
    conds = [[b.strip().lstrip("%") for b in branches.split(",")]
             for branches in re.findall(
                 r" conditional\(.*?branch_computations=\{([^}]*)\}", text)]
    moe = [bs for bs in conds if any(
        re.search(r"%t?gmm(?:\.\d+)? = \S+ custom-call\(", comps[b])
        for b in bs)]
    assert len(moe) == 4
    for all_rows, bounded in moe:
        for b in (all_rows, bounded):
            for line in comps[b].splitlines():
                m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) ([\w-]+)\(", line)
                op = re.search(r'op_name="(jit\([^"]*)"', line)
                if m and op and m.group(2) not in _PLUMBING:
                    assert _named(op.group(1), "moe_route") \
                        or _named(op.group(1), "moe_experts"), line
        assert any(_holds_rows(d, n) for d in re.findall(
            r"\w+\[([0-9,]+)\]", comps[all_rows]))
        for line in comps[bounded].splitlines():
            m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) [\w-]+\(", line)
            assert not (m and any(_holds_rows(d, n) for d in re.findall(
                r"\w+\[([0-9,]+)\]", m.group(1)))), line


def test_joyai_llm_flash_train_step_fits_one_v5e_and_names_its_depth(
        one_chip):
    """The cell's train step (1 x 8192 tokens, f32 master weights, bf16
    compute, remat dots) fits 90% of HBM by the compiler's own peak (11.53
    GiB, 12,379,285,504 bytes, when this was written; 12,212,600,832 once
    its expert layers bounded their held rows at half their rows, with
    the grouped matmuls' floor at 8), runs the splash
    kernels under ``attn_core`` and the grouped matmuls under
    ``moe_experts``, and the multi-token-prediction depth is named: every
    equation that ``lm.mtp_loss`` traced, forward, rematerialised and
    backward, carries ``mtp``, and the depth's attention kernels, expert
    kernels and head loops carry it in the compiled step, inside their own
    scopes.  Its expert layers branch on the held-row bound (32,768 of the
    65,536 rows): the bounded branch holds no array of 65,536 rows, and
    every op of both branches is named for the benchmark's MoE scopes."""
    cfg, mix, traced = _cell_step(one_chip, "joyai-llm-flash", "train_8k")
    assert cfg.mtp_depth == 1 and cfg.moe.scoring == "sigmoid"
    inside = [stack for stack, frames in _eqns(traced.jaxpr.jaxpr)
              if "mtp_loss" in frames]
    assert len(inside) > 100
    assert all(_named(stack, "mtp") for stack in inside)
    assert any("transpose(" in stack for stack in inside)
    compiled = traced.lower().compile()
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert 0 < peak < 0.9 * V5E_HBM_BYTES
    text = compiled.as_text()
    _assert_splash_attention(text)
    gmm = _assert_grouped_matmuls(text)
    assert any(_named(op, "mtp") for op in gmm)
    assert not all(_named(op, "mtp") for op in gmm)
    splash = re.findall(r"%splash_mha_\w+?\.\d+ = .*?custom-call\(.*?"
                        r'metadata=\{op_name="([^"]*)"', text, re.S)
    assert any(_named(op, "mtp") for op in splash)
    loops = re.findall(r' while\(.*?op_name="([^"]*)"', text)
    assert len([op for op in loops if _named(op, "mtp")
                and _named(op, "head")]) == 2   # its head, both ways
    n = mix["batch"] * mix["seq_len"] * cfg.moe.num_experts_per_tok
    assert L.held_row_bound(cfg.moe, n) == n // 2 == 32768
    _assert_held_rows_bounded(text, n)


def _without_metadata(text: str) -> str:
    """A compiled module's text without what only describes where its ops
    came from: each op's ``metadata``, the tables of source files,
    functions and frames that it points into, and the source locations
    inside each Mosaic kernel's body (the body is parsed and printed
    without them, then hashed)."""
    import base64
    import hashlib

    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    ctx = ir.Context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True

    def body(m):
        with ctx:
            asm = ir.Module.parse(base64.b64decode(m.group(1))).operation \
                .get_asm(enable_debug_info=False)
        return '"body":"' + hashlib.sha256(asm.encode()).hexdigest() + '"'
    text = re.sub(r'"body":"([^"]+)"', body, text)
    text = re.sub(r', metadata=\{(?:[^{}"]|"[^"]*")*\}', "", text)
    out, skip = [], False
    for line in text.split("\n"):
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            skip = True
        elif skip and not line:
            skip = False
        elif not skip:
            out.append(line)
    return "\n".join(out)


# sha256 of ``_without_metadata`` of the two train steps above, as their
# fixtures compile them (JAX 0.9.0), recorded from a program without
# sigmoid routing, the router's correction bias or the
# multi-token-prediction depth: those must leave softmax-routed and dense
# models' steps as they are.  The digests pin the toolchain too: a change
# that alters either step on purpose, or a new JAX or XLA, re-records
# them as a matter of course (run the test, copy the digest it reads) and
# says why.
UNCHANGED_HLO = {
    "qwen_train":
        "17c3ecdc3373963a8dd34e2467553f5e3639e19f6729dfd60409ce40b2570f0d",
    "deepseek_train":
        "cef6c26bcd4012ac4d379cdeff1397342a6bb56e53f4bbb4f5965a7dcb73cbdd",
}


@pytest.mark.parametrize("fixture", sorted(UNCHANGED_HLO))
def test_train_step_compiles_as_before_sigmoid_routing_and_the_depth(
        fixture, request):
    import hashlib

    compiled = request.getfixturevalue(fixture)[-1]
    digest = hashlib.sha256(
        _without_metadata(compiled.as_text()).encode()).hexdigest()
    assert digest == UNCHANGED_HLO[fixture]


def test_dropless_moe_on_a_four_chip_mesh_keeps_xlas_ragged_dot(topo):
    """GSPMD does not partition a Mosaic call, so on a mesh of four
    described v5e chips (experts sharded on ``model``) the dropless train
    step keeps ``lax.ragged_dot``, which XLA partitions: it compiles, runs
    no megablox kernel, and gathers no expert's weights onto one chip.  Nor
    does it gather the head's weights, sharded by vocabulary: there the loss
    scans token chunks (``lm.chunked_xent``), not vocabulary chunks."""
    import numpy as np
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))
    cfg = dataclasses.replace(get_arch("deepseek-v2-lite").smoke,
                              param_dtype="float32", compute_dtype="bfloat16")
    assert cfg.moe.capacity_factor <= 0
    opt_cfg = OptimizerConfig()
    tokens = jax.ShapeDtypeStruct((4, 256), jnp.int32)
    with activation_rules(mesh):
        jitted, sh = steps_lib.jit_train_step(cfg, opt_cfg, mesh,
                                              {"tokens": tokens})
        params, opt_state = (
            jax.tree.map(lambda a, s: _on(s, a.shape, a.dtype), t, s)
            for t, s in zip(steps_lib.train_state_shapes(cfg, opt_cfg),
                            (sh["params"], sh["opt_state"])))
        text = jitted.lower(params, opt_state, {"tokens": _on(
            sh["batch"]["tokens"], tokens.shape, tokens.dtype)}
        ).compile().as_text()
    assert not re.findall(r"%t?gmm(?:\.\d+)? = \S+ custom-call\(", text)
    assert "ragged-dot" in text
    m = cfg.moe
    whole = {f"{m.num_experts},{cfg.d_model},{m.d_ff_expert}",
             f"{m.num_experts},{m.d_ff_expert},{cfg.d_model}"}
    gathered = re.findall(r"= \S+\[([0-9,]+)\]\S* all-gather", text)
    assert gathered and not any(dims.endswith(w) for dims in gathered
                                for w in whole)
    assert f"{cfg.d_model},{cfg.vocab_size}" not in gathered


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
