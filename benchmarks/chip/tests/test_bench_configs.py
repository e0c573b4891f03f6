"""CPU tests of what a configuration brings as files of its own.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests

A configuration file states its cut from the source (``reduced``,
``published``, ``program``) and the harness checks it against the
registered arch and the model-configs guide's floors; a reference
(``references/<config>.py``) and operation counts (``counts/<config>.py``)
are found by the configuration's name.  The last test runs a whole cell of
a cut configuration with a reference of its own at a small size.
"""
from __future__ import annotations

import copy
import dataclasses
import json
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
sys.path[:0] = [str(CHIP), str(ROOT / "src")]

import flops  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402


def _conf(name: str) -> dict:
    return json.loads((CHIP / "configs" / f"{name}.json").read_text())


QWEN, GRANITE = _conf("qwen1.5-0.5b"), _conf("granite-moe-1b-a400m")
TRAIN = json.loads((CHIP / "traffic" / "train_4k.json").read_text())


def _cut(conf: dict, name: str, **cut) -> dict:
    """``conf`` under another name with ``cut``'s keys cut from their
    values in ``conf``, each taken by the program field ``program``
    names for it."""
    out = copy.deepcopy(conf)
    out["name"] = name
    fields = {"num_hidden_layers": "num_layers", "vocab_size": "vocab_size"}
    for k, v in cut.items():
        out.setdefault("published", {})[k] = conf[k]
        out["reduced"] = out.get("reduced", []) + [k]
        out[k] = v
        if k in fields:
            out.setdefault("program", {})[fields[k]] = k
    return out


def _parent_program_config(conf: dict, traffic: dict):
    """The ModelConfig as the harness made it before files could cut."""
    from repro.core.config import get_arch

    cfg = get_arch(conf["arch"]).model
    return dataclasses.replace(
        cfg, norm_eps=float(conf["rms_norm_eps"]),
        attention=dataclasses.replace(cfg.attention,
                                      rope_theta=float(conf["rope_theta"])),
        param_dtype=traffic.get("param_dtype", "bfloat16"),
        compute_dtype=traffic.get("compute_dtype", "bfloat16"))


# ------------------------------------------------- the cut in the file


def test_cut_configuration_taken_from_new_files(tmp_path):
    """Depth and an eighth of the vocabulary cut, found by name in a
    layout of new files, none of the repository's edited."""
    for d in ("configs", "traffic", "limits"):
        (tmp_path / d).mkdir()
    conf = _cut(QWEN, "qwen-cut", num_hidden_layers=6, vocab_size=18992)
    (tmp_path / "configs" / "qwen-cut.json").write_text(json.dumps(conf))
    (tmp_path / "traffic" / "train_8k.json").write_text(
        json.dumps(dict(TRAIN, seq_len=8192)))
    (tmp_path / "limits" / "qwen-cut.train_8k.json").write_text(
        json.dumps({"limits": {"loss": 1e-3}}))
    bench = {"workloads": [{"name": "qwen-cut.train_8k", "config": "qwen-cut",
                            "traffic": "train_8k", "chips": 1, "why": "x"}],
             "end_to_end": [{"name": "setup_s"}], "per_layer": []}
    cell = harness.find_cell("qwen-cut.train_8k", bench, here=tmp_path)
    assert cell.here == tmp_path and cell.traffic["seq_len"] == 8192
    cfg = harness.program_config(cell.config, cell.traffic)
    whole = harness.program_config(QWEN, cell.traffic)
    assert (cfg.num_layers, cfg.vocab_size) == (6, 18992)
    assert cfg == dataclasses.replace(whole, num_layers=6, vocab_size=18992)


def _bad(case: str) -> dict:
    if case == "reduced_width_without_published":
        c = _cut(QWEN, "q", num_hidden_layers=6)
        del c["published"]["num_hidden_layers"]
    elif case == "published_width_differs":
        c = _cut(QWEN, "q", num_hidden_layers=6)
        c["published"]["num_hidden_layers"] = 28
    elif case == "unknown_program_field":
        c = _cut(QWEN, "q", num_hidden_layers=6)
        c["program"] = {"num_layerz": "num_hidden_layers"}
    elif case == "program_field_answers_no_key":
        c = _cut(QWEN, "q", num_hidden_layers=6)
        c["program"]["d_model"] = "hidden_size"
    elif case == "reduced_width_no_field_takes":
        c = _cut(QWEN, "q", num_hidden_layers=6)
        del c["program"]
    elif case == "floor_layers":
        c = _cut(QWEN, "q", num_hidden_layers=3)
    elif case == "floor_experts":
        c = _cut(GRANITE, "g", num_local_experts=4)
    else:
        assert case == "floor_vocabulary"
        c = _cut(QWEN, "q", vocab_size=18991)
    return c


@pytest.mark.parametrize("case", [
    "reduced_width_without_published", "published_width_differs",
    "unknown_program_field", "program_field_answers_no_key",
    "reduced_width_no_field_takes", "floor_layers", "floor_experts",
    "floor_vocabulary"])
def test_bad_cut_is_refused(case):
    with pytest.raises(harness.BenchError) as e:
        harness.program_config(_bad(case), TRAIN)
    if case == "unknown_program_field":
        assert "num_layerz" in str(e.value)


def test_deepseek_names_checked_and_dense_layers_not_counted():
    """DeepSeek-V2's own names (MLA, shared experts, a dense first layer;
    https://huggingface.co/deepseek-ai/DeepSeek-V2/blob/main/config.json)
    are checked against the registered arch.  5 layers are the dense one
    and 4 more; 4 layers are too few."""
    ds = {"num_hidden_layers": 60, "hidden_size": 5120,
          "intermediate_size": 12288, "moe_intermediate_size": 1536,
          "num_attention_heads": 128, "num_key_value_heads": 128,
          "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
          "qk_rope_head_dim": 64, "v_head_dim": 128, "n_routed_experts": 160,
          "n_shared_experts": 2, "num_experts_per_tok": 6,
          "first_k_dense_replace": 1, "vocab_size": 102400,
          "attention_bias": False, "tie_word_embeddings": False,
          "rope_theta": 10000, "rms_norm_eps": 1e-6}
    conf = dict(ds, name="deepseek-v2", arch="deepseek-v2-236b")
    ok = _cut(conf, "ds", num_hidden_layers=5, vocab_size=12800)
    cfg = harness.program_config(ok, TRAIN)
    assert (cfg.num_layers, cfg.vocab_size, cfg.moe.num_experts) == \
        (5, 12800, 160)
    with pytest.raises(harness.BenchError, match="4 layers"):
        harness.program_config(
            _cut(conf, "ds", num_hidden_layers=4), TRAIN)
    with pytest.raises(harness.BenchError, match="kv_lora_rank"):
        harness.program_config(dict(conf, kv_lora_rank=256), TRAIN)


@pytest.mark.parametrize("conf", [QWEN, GRANITE], ids=lambda c: c["name"])
def test_program_config_unchanged_for_whole_models(conf):
    assert harness.program_config(conf, TRAIN) == \
        _parent_program_config(conf, TRAIN)


# ------------------------------------------- reference and counts by name


@pytest.mark.parametrize("own", [True, False])
@pytest.mark.parametrize("kind,default", [("references", reference),
                                          ("counts", flops)])
def test_reference_and_counts_found_by_name(tmp_path, kind, default, own):
    if own:
        (tmp_path / kind).mkdir()
        (tmp_path / kind / "new-model.py").write_text("OWN = True\n")
    find = harness.reference_for if kind == "references" else \
        harness.counts_for
    mod = find({"name": "new-model"}, tmp_path)
    if own:
        assert mod.OWN and Path(mod.__file__) == \
            tmp_path / kind / "new-model.py"
    else:
        assert mod is default


def test_qwen_train_step_flops():
    """13.874 TFLOP a step of 1 x 4096 tokens (PERF.md section 5)."""
    n = harness.counts_for(QWEN).train_step_flops(QWEN, 1, 4096)
    assert n == pytest.approx(13.874e12, rel=1e-4)


# ------------------------------------------------- a whole cut cell


def test_cut_cell_with_own_reference_is_correct(tmp_path, monkeypatch):
    """A cell of a cut configuration (depth and vocabulary) whose reference
    is its own file, wrapping ``reference.py``, runs through ``run_cell``
    on the CPU and ends correct, checked by its own reference."""
    import jax
    from repro.core import config as config_lib

    import bench

    base = config_lib.get_arch("qwen1.5-0.5b")
    smoke = dataclasses.replace(base.smoke, num_layers=5)
    monkeypatch.setitem(config_lib._ARCH_REGISTRY, "qwen-deep-smoke",
                        lambda: dataclasses.replace(base, smoke=smoke))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    a = smoke.attention
    conf = {"name": "qwen-deep-smoke-cut", "arch": "qwen-deep-smoke",
            "size": "smoke", "hidden_size": smoke.d_model,
            "num_hidden_layers": smoke.num_layers,
            "num_attention_heads": a.num_heads,
            "num_key_value_heads": a.num_kv_heads, "head_dim": a.head_dim,
            "intermediate_size": smoke.d_ff, "vocab_size": smoke.vocab_size,
            "rope_theta": 1e6, "rms_norm_eps": 1e-6,
            "attention_bias": a.qkv_bias, "tie_word_embeddings": True}
    conf = _cut(conf, conf["name"], num_hidden_layers=4, vocab_size=64)
    (tmp_path / "references").mkdir()
    (tmp_path / "references" / "qwen-deep-smoke-cut.py").write_text(
        "import reference\n"
        "from reference import *  # noqa: F401,F403\n"
        "CALLS = []\n\n\n"
        "def train(*args):\n"
        "    CALLS.append(args[1])\n"
        "    return reference.train(*args)\n")
    limits = json.loads(
        (CHIP / "limits" / "qwen1.5-0.5b.train_4k.json").read_text())
    # At this size on the CPU the readings differ from the chip cell's, so
    # the limits are 3x wider, as in test_sound_train_run_is_correct.
    limits = {"limits": {k: 3 * v for k, v in limits["limits"].items()}}
    cell = harness.Cell("qwen-deep-smoke-cut.train", 1, conf,
                        dict(TRAIN, seq_len=256, batch=2), limits,
                        [{"name": "train_tok_s", "unit": "tokens/s"},
                         {"name": "setup_s", "unit": "s"}], [],
                        here=tmp_path)

    class Args:
        workload, seed, seconds, trace = cell.name, 2**31 + 9, 0.5, 0
    res = bench.run_cell(Args, cell=cell, devices=jax.devices())[0]
    assert res["correct"], res["checks"]
    own = harness.reference_for(conf, tmp_path)
    assert [(c.layers, c.vocab) for c in own.CALLS] == [(4, 64)]
