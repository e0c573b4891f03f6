"""DeepSeek-V2-Lite on the normal path: MLA with YaRN, the dropless MoE
over the experts held, and the benchmark configuration that cuts it to one
chip's share.

On the CPU at the registered smoke size (a dense first layer, two expert
layers with two shared experts, YaRN), in float32, whose products the CPU
computes in full: the program against
the configuration's plain reference (``benchmarks/chip/references/``),
YaRN against its formula, the expert shares against the uncut layer, and
the dropless path against a per-token loop.
"""
import dataclasses
import json
import math
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

from repro.core.config import get_arch  # noqa: E402
from repro.models import api  # noqa: E402
from repro.models import attention as ATT  # noqa: E402
from repro.models import layers as L  # noqa: E402

F32 = jnp.float32
CONF = json.loads((CHIP / "configs" / "deepseek-v2-lite.json").read_text())


def _smoke():
    return dataclasses.replace(get_arch("deepseek-v2-lite").smoke,
                               param_dtype="float32", compute_dtype="float32")


def _conf(cfg):
    """The configuration file's keys for a smoke-size ModelConfig."""
    a, m = cfg.attention, cfg.moe
    return dict(CONF, name="deepseek-v2-lite-smoke", size="smoke",
                hidden_size=cfg.d_model, num_hidden_layers=cfg.num_layers,
                num_attention_heads=a.num_heads, kv_lora_rank=a.kv_lora_rank,
                qk_nope_head_dim=a.qk_nope_head_dim,
                qk_rope_head_dim=a.qk_rope_head_dim,
                v_head_dim=a.v_head_dim, vocab_size=cfg.vocab_size,
                n_routed_experts=m.num_experts,
                num_experts_per_tok=m.num_experts_per_tok,
                first_k_dense_replace=m.first_k_dense, reduced=[],
                published={})


def _weights(shapes, seed):
    """Seeded weights as the benchmark draws them (``weights.py``), from
    NumPy: matrices normal / sqrt(fan-in), norm scales 1 + 0.1 normal."""
    rng = np.random.default_rng(seed)

    def draw(path, s):
        if path.endswith("/scale"):
            x = 1.0 + 0.1 * rng.standard_normal(s.shape)
        elif path.endswith("/table"):
            x = 0.02 * rng.standard_normal(s.shape)
        else:
            x = rng.standard_normal(s.shape) / np.sqrt(s.shape[-2])
        return jnp.asarray(x, s.dtype)
    paths = iter(weights.leaf_paths(shapes))
    return jax.tree.map(lambda s: draw(next(paths)[0], s), shapes)


def _moe_params(cfg, seed=0):
    return L.init_moe(jax.random.key(seed), cfg, F32)


# ------------------------------------------------ program and reference


def test_program_matches_reference_loss_and_gradients():
    cfg = _smoke()
    ref = harness.reference_for({"name": "deepseek-v2-lite"})
    rc = ref.RefConfig.from_file(_conf(cfg))
    assert rc.yarn and rc.held == rc.experts == cfg.moe.num_experts
    W = _weights(api.param_shapes(cfg), 5)
    B, S = 2, 40
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (B, S)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        (_, met), g = jax.jit(jax.value_and_grad(
            lambda p: api.loss_fn(p, cfg, {"tokens": toks}, remat="none"),
            has_aux=True))(W)
        parts = [ref.sequence_grad(ref.highest_mm, rc, W, toks[r],
                                   float(B * (S - 1)), float(B))
                 for r in range(B)]
    ref_loss = sum(float(t) for _, t in parts) / (B * (S - 1))
    assert float(met["loss"]) == pytest.approx(ref_loss, rel=1e-5)
    moe_layers = cfg.num_layers - cfg.moe.first_k_dense
    assert float(met["moe_held_assignments"]) == \
        B * S * cfg.moe.num_experts_per_tok * moe_layers
    ref_g = jax.device_get(jax.tree.map(jnp.add, parts[0][0], parts[1][0]))
    for (path, a), b in zip(weights.leaf_paths(jax.device_get(g)),
                            jax.tree.leaves(ref_g)):
        err = np.max(np.abs(a - b)) / np.max(np.abs(b))
        assert err < 1e-4, (path, err)


# ------------------------------------------------------------------ YaRN


def _hand_yarn(dim, base, factor, max_pos, fast, slow):
    corr = lambda rot: dim * math.log(max_pos / (rot * 2 * math.pi)) \
        / (2 * math.log(base))
    low = max(math.floor(corr(fast)), 0)
    high = min(math.ceil(corr(slow)), dim - 1)
    out = []
    for i in range(dim // 2):
        extra = base ** (-2 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(extra / factor * ramp + extra * (1 - ramp))
    return np.array(out)


def test_yarn_frequencies_and_scale_match_the_hand_formula():
    a = get_arch("deepseek-v2-lite").model.attention
    s = a.rope_scaling
    freqs, mult = L.yarn_freqs(a.qk_rope_head_dim, a.rope_theta, s)
    want = _hand_yarn(64, 10_000.0, 40.0, 4096, 32.0, 1.0)
    np.testing.assert_allclose(np.asarray(freqs), want, rtol=2e-6)
    assert mult == 1.0          # mscale == mscale_all_dim
    # the first dims keep their frequency, the last are divided by 40
    assert want[0] == 1.0 and want[-1] == pytest.approx(
        10_000.0 ** (-62 / 64) / 40)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert mscale ** 2 == pytest.approx(1.5896, abs=1e-4)
    assert ATT.mla_softmax_scale(a) == pytest.approx(192 ** -0.5 * mscale ** 2)
    # without rope_scaling, RoPE is what it always was
    x = jax.random.normal(jax.random.key(1), (1, 9, 2, 16))
    pos = jnp.arange(9)[None]
    inv = 1.0 / 1e6 ** (np.arange(0, 16, 2) / 16)
    ang = np.arange(9)[:, None, None] * inv
    x1, x2 = np.split(np.asarray(x[0]), 2, -1)
    plain = np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                            x1 * np.sin(ang) + x2 * np.cos(ang)], -1)
    np.testing.assert_allclose(np.asarray(L.apply_rope(x, pos, 1e6)[0]),
                               plain, rtol=1e-5, atol=1e-6)


# ------------------------------------------------- the experts held here


def _share(p, E, lo, hi):
    """The parameters of the chip that holds experts [lo, hi): its
    router's outputs rolled so that they come first, and their weights."""
    cols = [(lo + i) % E for i in range(E)]
    q = {k: p[k][lo:hi] for k in ("w_up", "w_gate", "w_down")}
    q["router"] = {"w": p["router"]["w"][:, cols]}
    return q


@pytest.mark.parametrize("held", [2, 4])
def test_expert_shares_add_up_to_the_uncut_layer(held):
    cfg = _smoke()
    m = cfg.moe
    p = _moe_params(cfg)
    x = jax.random.normal(jax.random.key(2), (2, 24, cfg.d_model))
    mq = dataclasses.replace(m, experts_held=held)
    shares = [_share(p, m.num_experts, lo, lo + held)
              for lo in range(0, m.num_experts, held)]
    (whole, stats), parts, shared = jax.jit(lambda p, qs, x: (
        L.apply_moe(p, x, cfg, compute_dtype=F32),
        [L.dropless_moe(q, x, mq, F32) for q in qs],
        L.apply_ffn(p["shared"], x, "swiglu", F32)))(p, shares, x)
    total = sum(y for y, _ in parts) + shared      # shared expert once
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=1e-5)
    assert sum(float(s["held"]) for _, s in parts) == float(stats["held"]) \
        == x.shape[0] * x.shape[1] * m.num_experts_per_tok
    for _, s in parts:      # every chip computes the same balance loss
        assert float(s["aux"]) == pytest.approx(float(stats["aux"]))


def _per_token(p, m, x):
    """Each token through each of its top-k experts, one at a time."""
    out = np.zeros(x.shape, np.float32)
    xs = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    r = xs @ np.asarray(p["router"]["w"], np.float64)
    probs = np.exp(r - r.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    flat = out.reshape(-1, x.shape[-1])
    for t in range(xs.shape[0]):
        top = np.argsort(-probs[t], kind="stable")[:m.num_experts_per_tok]
        g = probs[t, top]
        if m.norm_topk_prob:
            g = g / g.sum()
        for e, ge in zip(top, g):
            if e >= m.held:
                continue
            w = {k: np.asarray(p[k][e], np.float64)
                 for k in ("w_gate", "w_up", "w_down")}
            a = xs[t] @ w["w_gate"]
            h = a / (1 + np.exp(-a)) * (xs[t] @ w["w_up"])
            flat[t] += ge * (h @ w["w_down"])
    return out


def test_nothing_is_dropped_when_every_token_picks_the_same_experts():
    cfg = _smoke()
    m, E, K = cfg.moe, cfg.moe.num_experts, cfg.moe.num_experts_per_tok
    p = _moe_params(cfg)
    # every token's router logits rank experts 0 < 1 < ... < K-1 first
    bias = np.zeros((cfg.d_model, E), np.float32)
    bias[:, :K] = np.arange(K, 0, -1) * 0.5
    p["router"]["w"] = p["router"]["w"] * 0.01 + bias
    x = 1.0 + 0.1 * jax.random.normal(jax.random.key(3), (2, 32, cfg.d_model))
    routed = {k: v for k, v in p.items() if k != "shared"}
    # the capacity path renormalises its gates; so does the loop it is held to
    m_norm = dataclasses.replace(m, norm_topk_prob=True)
    (y, stats), (capped, _) = jax.jit(lambda p, x: (
        L.dropless_moe(p, x, m, F32),
        L.apply_moe(p, x, dataclasses.replace(cfg, moe=m_norm),
                    capacity_factor=1.25, compute_dtype=F32)))(routed, x)
    want = _per_token(p, m, x)
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5, rtol=1e-4)
    assert float(stats["held"]) == 2 * 32 * K
    assert float(stats["max_load"]) == pytest.approx(E / K)
    # the capacity path drops most of these assignments
    assert float(jnp.max(jnp.abs(capped - _per_token(p, m_norm, x)))) > 0.1


@pytest.mark.parametrize("change", [{"experts_held": 2},
                                    {"norm_topk_prob": False}])
def test_capacity_path_refuses_what_only_the_dropless_path_computes(change):
    """The capacity path holds every expert and renormalises its gates;
    asked for a share of the experts or raw gates, it refuses."""
    cfg = dataclasses.replace(get_arch("granite-moe-1b-a400m").smoke,
                              param_dtype="float32", compute_dtype="float32")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, **change))
    p = _moe_params(cfg)
    x = jnp.ones((1, 8, cfg.d_model))
    with pytest.raises(ValueError, match="dropless"):
        L.apply_moe(p, x, cfg, capacity_factor=1.25, compute_dtype=F32)


def test_granite_smoke_moe_gives_todays_outputs():
    """Granite renormalises its top-k gates; the grouped path computes
    what the one-hot dropless path computed (capacity S*K: none dropped)."""
    cfg = dataclasses.replace(get_arch("granite-moe-1b-a400m").smoke,
                              param_dtype="float32", compute_dtype="float32")
    m = cfg.moe
    assert m.norm_topk_prob and m.held == m.num_experts
    p = _moe_params(cfg, seed=4)
    x = jax.random.normal(jax.random.key(5), (1, 48, cfg.d_model))
    (y, stats), (old, old_stats) = jax.jit(lambda p, x: (
        L.apply_moe(p, x, cfg, capacity_factor=0.0, compute_dtype=F32),
        L.apply_moe(p, x, cfg, capacity_factor=float(m.num_experts),
                    compute_dtype=F32)))(p, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(old), atol=1e-5)
    np.testing.assert_allclose(np.asarray(y), _per_token(p, m, x),
                               atol=2e-5, rtol=1e-4)
    assert float(stats["aux"]) == pytest.approx(float(old_stats["aux"]))


# ------------------------------------------------------ the balance loss


def _switch_aux(probs, top, E, K):
    count = np.zeros(E)
    for t in top.reshape(-1):
        count[t] += 1
    return E * np.sum(count / (top.shape[0] * K) * probs.mean(0))


def test_dropless_balance_loss_is_per_sequence_capacity_path_unchanged():
    """The dropless path averages each sequence's own balance loss; the
    capacity path keeps pooling its routing groups, and its output keeps
    dropping over capacity in token order."""
    cfg = dataclasses.replace(get_arch("granite-moe-1b-a400m").smoke,
                              param_dtype="float32", compute_dtype="float32")
    m, E, K = cfg.moe, cfg.moe.num_experts, cfg.moe.num_experts_per_tok
    p = _moe_params(cfg, seed=6)
    x = jax.random.normal(jax.random.key(7), (2, 64, cfg.d_model))
    (_, drop), (y_cap, cap) = jax.jit(lambda p, x: (
        L.apply_moe(p, x, cfg, capacity_factor=0.0, compute_dtype=F32),
        L.apply_moe(p, x, cfg, capacity_factor=0.5,
                    compute_dtype=F32)))(p, x)
    r = np.asarray(x, np.float64) @ np.asarray(p["router"]["w"], np.float64)
    probs = np.exp(r - r.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    top = np.argsort(-probs, -1, kind="stable")[..., :K]
    per_seq = np.mean([_switch_aux(probs[b], top[b], E, K) for b in range(2)])
    pooled = _switch_aux(probs.reshape(-1, E), top.reshape(-1, K), E, K)
    assert abs(per_seq - pooled) > 1e-4
    assert float(drop["aux"]) == pytest.approx(per_seq, rel=1e-5)
    assert float(cap["aux"]) == pytest.approx(pooled, rel=1e-5)
    # capacity 16 per expert and group; queues fill token by token, then by
    # choice; the gates are renormalised over the top k
    C = L.moe_capacity(64, E, K, 0.5)
    want = np.zeros(x.shape)
    for b in range(2):
        fill = np.zeros(E, int)
        keep = np.zeros((64, K), bool)
        for t in range(64):
            for k in range(K):
                e = top[b, t, k]
                keep[t, k] = fill[e] < C
                fill[e] += 1
        g = np.take_along_axis(probs[b], top[b], -1)
        g = g / g.sum(-1, keepdims=True) * keep
        for t in range(64):
            for k in range(K):
                if keep[t, k]:
                    w = {n: np.asarray(p[n][top[b, t, k]], np.float64)
                         for n in ("w_gate", "w_up", "w_down")}
                    xt = np.asarray(x[b, t], np.float64)
                    a = xt @ w["w_gate"]
                    want[b, t] += g[t, k] * ((a / (1 + np.exp(-a))
                                              * (xt @ w["w_up"]))
                                             @ w["w_down"])
    assert not np.all(keep)
    np.testing.assert_allclose(np.asarray(y_cap), want, atol=2e-5, rtol=1e-4)


# ------------------------------------------- the benchmark configuration


def test_program_config_takes_the_cut_and_the_arch_matches_the_file():
    traffic = json.loads((CHIP / "traffic" / "train_8k.json").read_text())
    cfg = harness.program_config(CONF, traffic)
    full = get_arch("deepseek-v2-lite").model
    assert (cfg.num_layers, cfg.vocab_size, cfg.moe.experts_held) == \
        (6, 12_800, 8)
    assert (full.num_layers, full.vocab_size, full.moe.num_experts) == \
        tuple(CONF["published"][k] for k in
              ("num_hidden_layers", "vocab_size", "n_routed_experts"))
    assert cfg.moe.num_experts == 64 and cfg.moe.held == 8
    # the keys harness._widths does not check
    rs = CONF["rope_scaling"]
    assert rs["type"] == "yarn"
    assert dataclasses.asdict(full.attention.rope_scaling) == {
        k: rs[k] for k in ("factor", "original_max_position_embeddings",
                           "beta_fast", "beta_slow", "mscale",
                           "mscale_all_dim")}
    assert full.moe.norm_topk_prob == CONF["norm_topk_prob"] is False
    assert CONF["routed_scaling_factor"] == 1     # the program has no such factor
    assert full.moe.aux_loss_coef == CONF["aux_loss_alpha"]
    assert full.moe.capacity_factor <= 0          # dropless
    # the only scoring, selection and balance loss the program has
    assert (CONF["scoring_func"], CONF["topk_method"], CONF["seq_aux"]) == \
        ("softmax", "greedy", True)
    assert CONF["n_group"] == CONF["topk_group"] == 1
    assert full.attention.q_lora_rank == 0 and CONF["q_lora_rank"] is None
    assert not full.tie_embeddings and full.norm_eps == CONF["rms_norm_eps"]
