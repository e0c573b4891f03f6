"""JoyAI-LLM-Flash (the DeepSeek-V3 block) on the normal path: sigmoid
routing with a correction bias that the step rebalances, the
multi-token-prediction depth, q-LoRA MLA, and the benchmark configuration
that cuts it to one chip's share.

On the CPU at the registered smoke size (a dense first layer, two expert
layers of 32 experts with one shared, the depth), in float32, whose
products the CPU computes in full: the program against the
configuration's plain reference (``benchmarks/chip/references/``), over
three train steps with the bias rule too; the gates and the bias against
a per-token loop; AdamW leaving the bias alone; the 32 expert shares against
the uncut reference layer.
"""
import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import harness  # noqa: E402
import weights  # noqa: E402

from repro.core.config import OptimizerConfig, get_arch  # noqa: E402
from repro.launch import steps as steps_lib  # noqa: E402
from repro.models import api  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models import lm as LM  # noqa: E402
from repro.optim import adamw  # noqa: E402

F32 = jnp.float32
CONF = json.loads((CHIP / "configs" / "joyai-llm-flash.json").read_text())
REF = harness.reference_for({"name": "joyai-llm-flash"}, CHIP)
OPT = {"lr": 1e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
       "grad_clip": 1.0, "warmup_steps": 1, "total_steps": 10,
       "schedule": "cosine"}
BIAS = ("stack/periods/sub0/ffn_moe/router/bias",
        "mtp/block/ffn_moe/router/bias")


def _smoke():
    return dataclasses.replace(get_arch("joyai-llm-flash").smoke,
                               param_dtype="float32", compute_dtype="float32")


def _conf(cfg):
    """The configuration file's keys for a smoke-size ModelConfig, every
    expert held."""
    a, m = cfg.attention, cfg.moe
    return dict(CONF, name="joyai-llm-flash-smoke", size="smoke",
                hidden_size=cfg.d_model, num_hidden_layers=cfg.num_layers,
                num_attention_heads=a.num_heads, q_lora_rank=a.q_lora_rank,
                kv_lora_rank=a.kv_lora_rank,
                qk_nope_head_dim=a.qk_nope_head_dim,
                qk_rope_head_dim=a.qk_rope_head_dim,
                v_head_dim=a.v_head_dim, vocab_size=cfg.vocab_size,
                n_routed_experts=m.num_experts,
                num_experts_per_tok=m.num_experts_per_tok,
                first_k_dense_replace=m.first_k_dense, reduced=[],
                published={"n_routed_experts": m.num_experts})


def _tokens(cfg, B, S, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (B, S)), jnp.int32)


def _leaf(tree, path):
    for k in path.split("/"):
        tree = tree[k]
    return tree


# ------------------------------------------------ program and reference


def test_program_matches_reference_loss_and_gradients():
    cfg = _smoke()
    rc = REF.RefConfig.from_file(_conf(cfg))
    assert rc.held == rc.experts == cfg.moe.num_experts
    assert (rc.routed_scale, rc.bias_rate, rc.mtp_weight, rc.aux_alpha) == (
        cfg.moe.routed_scaling_factor, cfg.moe.bias_update_rate,
        cfg.mtp_loss_coef, cfg.moe.aux_loss_coef)
    W = weights.make_params(api.param_shapes(cfg), 5)
    B, S = 2, 40
    toks = _tokens(cfg, B, S)
    with jax.default_matmul_precision("highest"):
        (_, met), g = jax.jit(jax.value_and_grad(
            lambda p: api.loss_fn(p, cfg, {"tokens": toks}, remat="none"),
            has_aux=True))(W)
        parts = [REF.sequence_grad(REF.highest_mm, rc, W, toks[r],
                                   float(B * (S - 1)), float(B * (S - 2)),
                                   float(B)) for r in range(B)]
    ref_loss = sum(float(t) for _, t, _ in parts) / (B * (S - 1))
    assert float(met["loss"]) == pytest.approx(ref_loss, rel=1e-5)
    # the depth's own loss is in the gradient: its leaves are not zero
    assert float(met["mtp_loss"]) > 0
    moe_layers = cfg.num_layers - cfg.moe.first_k_dense + cfg.mtp_depth
    assert float(met["moe_held_assignments"]) == \
        B * S * cfg.moe.num_experts_per_tok * moe_layers
    loads = jax.tree.map(jnp.add, parts[0][2], parts[1][2])
    for path, part in zip(BIAS, ("stack", "mtp")):
        np.testing.assert_array_equal(_leaf(met["router_loads"], path),
                                      loads[part])
    ref_g = jax.device_get(jax.tree.map(jnp.add, parts[0][0], parts[1][0]))
    for (path, a), b in zip(weights.leaf_paths(jax.device_get(g)),
                            jax.tree.leaves(ref_g)):
        if path.endswith("/router/bias"):       # no gradient reaches it
            assert not np.any(a) and not np.any(b), path
            continue
        err = np.max(np.abs(a - b)) / np.max(np.abs(b))
        assert err < 1e-4, (path, err)


def test_three_steps_with_the_bias_rule_match_the_reference():
    """Three train steps of the program (AdamW, then the bias moved by the
    step's loads) against the reference's own AdamW and bias rule: the
    losses, the change of every leaf, and the biases exactly."""
    cfg = _smoke()
    rc = REF.RefConfig.from_file(_conf(cfg))
    shapes = api.param_shapes(cfg)
    W0 = weights.make_params(shapes, 7)
    batches = [_tokens(cfg, 2, 32, seed=i) for i in range(3)]
    opt_cfg = OptimizerConfig(**OPT)
    step = jax.jit(steps_lib.make_train_step(cfg, opt_cfg, remat="none"))
    with jax.default_matmul_precision("highest"):
        p, o = W0, adamw.init_opt_state(W0, opt_cfg)
        losses = []
        for b in batches:
            p, o, m = step(p, o, {"tokens": b})
            losses.append(float(m["loss"]))
        ref_losses, _, W = REF.train(REF.highest_mm, rc,
                                     jax.tree.map(jnp.copy, W0), batches,
                                     OPT, 3)
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-5)
    p, W, W0 = (jax.device_get(t) for t in (p, W, W0))
    for path in BIAS:
        moved = _leaf(p, path) - _leaf(W0, path)
        # three steps of +-rate (or 0 where a load sat on the mean)
        assert np.any(moved) and np.all(np.abs(moved) <= 3 * 1e-3 + 1e-7)
        np.testing.assert_array_equal(_leaf(p, path), _leaf(W, path))
    # each leaf's change as a whole: Adam moves an entry whose gradient is
    # near zero by about +-lr whatever its size, so single entries may
    # differ by rounding far more than the leaf does (3e-4 at most here)
    for (path, a), (_, b), (_, a0) in zip(weights.leaf_paths(p),
                                          weights.leaf_paths(W),
                                          weights.leaf_paths(W0)):
        change = b - a0
        err = np.linalg.norm((a - a0) - change) / np.linalg.norm(change)
        assert err < 1e-3, (path, err)
    assert float(m["moe_bias_absmax"]) == pytest.approx(max(
        float(np.max(np.abs(_leaf(p, path)))) for path in BIAS))


def test_no_prediction_depth_gives_todays_loss():
    """Without the depth the loss is what the model computed before there
    was one, bit for bit: the main head's cross-entropy plus the balance
    loss; with it, the main loss is the same number."""
    cfg = _smoke()
    cfg0 = dataclasses.replace(cfg, mtp_depth=0)
    W = weights.make_params(api.param_shapes(cfg), 3)
    W0 = {k: v for k, v in W.items() if k != "mtp"}
    assert jax.tree.structure(W0) == \
        jax.tree.structure(api.param_shapes(cfg0))
    toks = _tokens(cfg, 2, 40)

    def both(W, W0):
        total0, m0 = api.loss_fn(W0, cfg0, {"tokens": toks})
        _, m1 = api.loss_fn(W, cfg, {"tokens": toks})
        x, stats = LM.trunk(W0, cfg0, {"tokens": toks})
        h = L.apply_norm(W0["final_norm"], x, cfg0.norm_eps)
        today = LM.head_xent(W0, cfg0, h[:, :-1], toks[:, 1:]) \
            + cfg0.moe.aux_loss_coef * stats["aux"]
        return total0, m0, m1, today
    total0, m0, m1, today = jax.jit(both)(W, W0)
    assert float(total0) == float(today)
    assert float(m0["loss"]) == float(m1["loss"])
    assert "mtp_loss" not in m0 and float(m1["mtp_loss"]) > 0


# ------------------------------------------------------ routing and bias


def _per_token(p, m, x, bias):
    """Each token through each of its top-k experts, one at a time: top k
    of sigmoid affinity + bias, gates from the affinities."""
    out = np.zeros(x.shape, np.float64)
    xs = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    s = 1 / (1 + np.exp(-(xs @ np.asarray(p["router"]["w"], np.float64))))
    flat = out.reshape(-1, x.shape[-1])
    for t in range(xs.shape[0]):
        top = np.argsort(-(s[t] + bias), kind="stable")[:m.num_experts_per_tok]
        g = s[t, top] / s[t, top].sum() * m.routed_scaling_factor
        for e, ge in zip(top, g):
            w = {k: np.asarray(p[k][e], np.float64)
                 for k in ("w_gate", "w_up", "w_down")}
            a = xs[t] @ w["w_gate"]
            h = a / (1 + np.exp(-a)) * (xs[t] @ w["w_up"])
            flat[t] += ge * (h @ w["w_down"])
    return out


def test_sigmoid_gates_are_renormalised_and_scaled_and_bias_only_selects():
    cfg = _smoke()
    m, E, K = cfg.moe, cfg.moe.num_experts, cfg.moe.num_experts_per_tok
    p = {k: v for k, v in L.init_moe(jax.random.key(4), cfg, F32).items()
         if k != "shared"}
    x = jax.random.normal(jax.random.key(5), (2, 24, cfg.d_model))
    favour = np.zeros(E, np.float32)
    favour[E - K:] = 10.0          # every token picks the last K experts
    runs = []
    for bias in (np.zeros(E, np.float32), favour):
        q = dict(p, router=dict(p["router"], bias=jnp.asarray(bias)))
        y, stats = jax.jit(lambda q, x: L.dropless_moe(q, x, m, F32))(q, x)
        np.testing.assert_allclose(np.asarray(y), _per_token(q, m, x, bias),
                                   atol=2e-5, rtol=1e-4)
        runs.append(np.asarray(stats["loads"]["router"]["bias"]))
    assert runs[0].sum() == runs[1].sum() == 2 * 24 * K
    assert not np.array_equal(runs[0], runs[1])
    np.testing.assert_array_equal(runs[1][E - K:], 2 * 24)


def test_bias_rule_moves_towards_balance():
    loads = {"a": {"ffn_moe": {"router": {"bias": jnp.asarray(
                 [[1.0, 3.0, 2.0], [2.0, 2.0, 2.0]])}}},
             "b": {"ffn_moe": {"router": {"bias": jnp.asarray(
                 [0.0, 4.0, 2.0])}}}}
    params = {"a": {"ffn_moe": {"router": {"bias": jnp.zeros((2, 3)),
                                           "w": jnp.ones((2, 4, 3))}}},
              "b": {"ffn_moe": {"router": {"bias": jnp.full((3,), -0.5),
                                           "w": jnp.ones((4, 3))}}}}
    new, biggest = L.rebalance_router_bias(params, loads, 0.01)
    np.testing.assert_allclose(new["a"]["ffn_moe"]["router"]["bias"],
                               [[0.01, -0.01, 0.0], [0.0, 0.0, 0.0]])
    np.testing.assert_allclose(new["b"]["ffn_moe"]["router"]["bias"],
                               [-0.49, -0.51, -0.5])
    assert float(biggest) == pytest.approx(0.51)
    assert new["a"]["ffn_moe"]["router"]["w"] is \
        params["a"]["ffn_moe"]["router"]["w"]


def test_adamw_leaves_the_correction_bias_alone():
    """No gradient reaches a router's correction bias, and AdamW decays
    no bias: with the gradient the loss gives it (zero) and weight decay
    on, AdamW keeps the bias bit for bit, its moments stay zero and the
    clip norm is that of the other leaves, while it updates, decays and
    clips every other leaf."""
    cfg = _smoke()
    p = L.init_moe(jax.random.key(1), cfg, F32)
    p["router"]["bias"] = jnp.full((cfg.moe.num_experts,), 0.5)
    x = jax.random.normal(jax.random.key(2), (1, 16, cfg.d_model))
    g = jax.grad(lambda q: jnp.sum(L.dropless_moe(q, x, cfg.moe, F32)[0])
                 )(p)["router"]["bias"]
    assert g.shape == (cfg.moe.num_experts,) and not np.any(g)
    params = {"router": {"w": jnp.ones((4, 3)), "bias": jnp.full((3,), 0.5)},
              "w": {"w": jnp.ones((4, 4))}}
    grads = {"router": {"w": jnp.full((4, 3), 0.1), "bias": jnp.zeros(3)},
             "w": {"w": jnp.full((4, 4), 0.1)}}
    opt_cfg = OptimizerConfig(lr=0.1, warmup_steps=1, grad_clip=1.0,
                              weight_decay=0.5)
    state = adamw.init_opt_state(params, opt_cfg)
    new, state, met = adamw.adamw_update(params, grads, state, opt_cfg)
    np.testing.assert_array_equal(new["router"]["bias"], 0.5)
    assert not np.any(state["m"]["router"]["bias"])
    assert not np.any(state["v"]["router"]["bias"])
    assert float(met["grad_norm"]) == pytest.approx(0.1 * np.sqrt(28))
    assert np.all(np.asarray(new["router"]["w"]) < 1.0)


# ------------------------------------------------- the experts held here


def _share(p, E, lo, hi):
    """The parameters of the chip that holds experts [lo, hi): its
    router's outputs and bias rolled so that they come first, and their
    weights."""
    cols = [(lo + i) % E for i in range(E)]
    q = {k: p[k][lo:hi] for k in ("w_up", "w_gate", "w_down")}
    q["router"] = {"w": p["router"]["w"][:, cols],
                   "bias": p["router"]["bias"][jnp.asarray(cols)]}
    return q


def test_32_expert_shares_add_up_to_the_uncut_reference_layer():
    """The deployment's cut at the smoke size: 32 experts, one held a
    chip.  The 32 shares' results, with the shared expert counted once,
    add up to the reference's uncut layer; each share counts the same
    loads over all experts (up to its roll) and the same balance loss."""
    cfg = _smoke()
    m, E = cfg.moe, cfg.moe.num_experts
    assert E == 32
    p = L.init_moe(jax.random.key(6), cfg, F32)
    p["router"]["bias"] = 0.2 * jax.random.normal(jax.random.key(7), (E,))
    x = jax.random.normal(jax.random.key(8), (1, 48, cfg.d_model))
    mq = dataclasses.replace(m, experts_held=1)
    shares = [_share(p, E, lo, lo + 1) for lo in range(E)]
    parts, shared = jax.jit(lambda qs, x: (
        [L.dropless_moe(q, x, mq, F32) for q in qs],
        L.apply_ffn(p["shared"], x, "swiglu", F32)))(shares, x)
    rc = REF.RefConfig.from_file(_conf(cfg))
    with jax.default_matmul_precision("highest"):
        whole, aux, count = jax.jit(lambda p, h: REF.moe(
            REF.highest_mm, rc, p, h))(p, x[0])
    total = sum(y for y, _ in parts) + shared
    np.testing.assert_allclose(np.asarray(total[0]), np.asarray(whole),
                               atol=1e-5)
    for lo, (_, s) in enumerate(parts):
        np.testing.assert_array_equal(
            np.roll(np.asarray(s["loads"]["router"]["bias"]), lo), count)
        assert float(s["aux"]) == pytest.approx(float(aux), rel=1e-5)
    assert sum(float(s["held"]) for _, s in parts) == \
        48 * m.num_experts_per_tok


@pytest.mark.parametrize("floor", [1.0, 4.0, 1e3])
def test_grouped_rows_floor_changes_no_number(floor):
    """The floor only lets the grouped matmuls run on over zero rows: with
    8 of 32 experts held, the layer's output, its stats and every
    gradient are those without it, bit for bit, below, near and above
    the rows routed (86 here, against 64 expected)."""
    cfg = _smoke()
    m = dataclasses.replace(cfg.moe, experts_held=8)
    p = L.init_moe(jax.random.key(1), dataclasses.replace(cfg, moe=m), F32)
    p["router"]["bias"] = 0.1 * jax.random.normal(jax.random.key(3), (32,))
    x = jax.random.normal(jax.random.key(2), (1, 64, cfg.d_model))

    def run(m):
        def f(q):
            y, s = L.dropless_moe(q, x, m, jnp.bfloat16)
            return jnp.sum(y.astype(F32) ** 2), s
        return jax.jit(jax.value_and_grad(f, has_aux=True))(p)
    (v0, s0), g0 = run(m)
    (v1, s1), g1 = run(dataclasses.replace(m, grouped_rows_floor=floor))
    assert float(s0["held"]) == 86
    assert float(v1) == float(v0)
    for a, b in zip(jax.tree.leaves((s0, g0)), jax.tree.leaves((s1, g1))):
        np.testing.assert_array_equal(a, b)


def test_capacity_path_refuses_sigmoid_scoring():
    cfg = _smoke()
    p = L.init_moe(jax.random.key(0), cfg, F32)
    with pytest.raises(ValueError, match="dropless"):
        L.apply_moe(p, jnp.ones((1, 8, cfg.d_model)), cfg,
                    capacity_factor=1.25, compute_dtype=F32)


# ------------------------------------------- the benchmark configuration


def test_program_config_takes_the_cut_and_the_arch_matches_the_file():
    traffic = json.loads((CHIP / "traffic" / "train_8k.json").read_text())
    cfg = harness.program_config(CONF, traffic)
    full = get_arch("joyai-llm-flash").model
    assert (cfg.num_layers, cfg.vocab_size, cfg.moe.experts_held) == \
        (5, 16_160, 8)
    assert (full.num_layers, full.vocab_size, full.moe.num_experts) == \
        tuple(CONF["published"][k] for k in
              ("num_hidden_layers", "vocab_size", "n_routed_experts"))
    assert cfg.moe.num_experts == 256 and cfg.moe.held == 8
    # the keys harness._widths does not check
    assert (CONF["scoring_func"], CONF["topk_method"]) == \
        ("sigmoid", "noaux_tc") and full.moe.scoring == "sigmoid"
    assert CONF["n_group"] == CONF["topk_group"] == 1
    assert full.moe.norm_topk_prob == CONF["norm_topk_prob"] is True
    assert full.moe.routed_scaling_factor == CONF["routed_scaling_factor"]
    assert full.mtp_depth == CONF["num_nextn_predict_layers"] == 1
    for field, key in (("aux_loss_coef", "aux_loss_alpha"),
                       ("bias_update_rate", "bias_update_speed")):
        assert getattr(full.moe, field) == getattr(cfg.moe, field) \
            == CONF[key] and key in CONF["assumed"]
    assert full.mtp_loss_coef == cfg.mtp_loss_coef == \
        CONF["mtp_loss_weight"] and "mtp_loss_weight" in CONF["assumed"]
    assert full.moe.capacity_factor <= 0          # dropless
    assert CONF["rope_scaling"] is None and full.attention.rope_scaling is None
    assert cfg.attention.rope_theta == CONF["rope_theta"] == 32_000_000
    assert not full.tie_embeddings and full.norm_eps == CONF["rms_norm_eps"]
    counts = harness.counts_for(CONF, CHIP)
    assert counts.total_params(CONF) == api.param_count(cfg) == 491_695_360
