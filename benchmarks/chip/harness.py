"""Finds a cell's files by name and holds what both loops share.

``BENCHMARK.json`` (at the checkout's root) names each cell's configuration
and traffic mix.  The files are found by those names alone:

* ``configs/<config>.json``: the configuration as it is run;
* ``traffic/<mix>.json``: the mix's kind (``serve`` or ``train``) and its
  parameters;
* ``limits/<cell>.json``: the limits of the cell's correctness check;
* ``metrics/<metric>.py``: one reader per per-layer metric, with
  ``read(ctx) -> float | None``.

A later cell, mix or metric is a new file and a new ``BENCHMARK.json``
entry; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class BenchError(Exception):
    """The run cannot give a result (no chip, unknown cell, bad file)."""


def load_json(path: Path) -> Dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise BenchError(f"missing file {path}") from None


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def _reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, bench: Optional[Dict] = None,
              here: Path = HERE) -> Cell:
    bench = bench if bench is not None else load_json(ROOT / "BENCHMARK.json")
    rows = [w for w in bench["workloads"] if w["name"] == name]
    if not rows:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    w = rows[0]
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in names and _reports(m, name)]
    return Cell(name=name, chips=w["chips"],
                config=load_json(here / "configs" / f"{w['config']}.json"),
                traffic=load_json(here / "traffic" / f"{w['traffic']}.json"),
                limits=load_json(here / "limits" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer)


def cell_from_files(name: str, here: Path = HERE) -> Cell:
    """A cell that ``BENCHMARK.json`` does not list, named
    ``<config>.<mix>``, for calibration runs; it has no limits."""
    conf, mix = name.rsplit(".", 1)
    lim = here / "limits" / f"{name}.json"
    return Cell(name=name, chips=1,
                config=load_json(here / "configs" / f"{conf}.json"),
                traffic=load_json(here / "traffic" / f"{mix}.json"),
                limits=load_json(lim) if lim.exists() else {"limits": {}},
                end_to_end=[], per_layer=[])


def metric_reader(name: str, here: Path = HERE):
    path = here / "metrics" / f"{name}.py"
    if not path.exists():
        raise BenchError(f"no reader {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def program_config(conf: Dict, traffic: Dict):
    """The program's ModelConfig for a configuration file: the registered
    arch, with the published rope theta and norm epsilon, and the dtypes
    the mix states.  Every width is checked against the file."""
    from repro.core.config import get_arch

    spec = get_arch(conf["arch"])
    cfg = spec.smoke if conf.get("size") == "smoke" else spec.model
    a = cfg.attention
    want = {"hidden_size": cfg.d_model, "num_hidden_layers": cfg.num_layers,
            "num_attention_heads": a.num_heads,
            "num_key_value_heads": a.num_kv_heads, "head_dim": a.head_dim,
            "vocab_size": cfg.vocab_size,
            "attention_bias": a.qkv_bias,
            "tie_word_embeddings": cfg.tie_embeddings}
    if cfg.moe:
        want.update(num_local_experts=cfg.moe.num_experts,
                    num_experts_per_tok=cfg.moe.num_experts_per_tok,
                    intermediate_size=cfg.moe.d_ff_expert)
    else:
        want["intermediate_size"] = cfg.d_ff
    bad = {k: (v, conf.get(k)) for k, v in want.items() if conf.get(k) != v}
    if bad:
        raise BenchError(f"registered {conf['arch']} differs from "
                         f"{conf['name']}.json: {bad}")
    cfg = dataclasses.replace(
        cfg, norm_eps=float(conf["rms_norm_eps"]),
        attention=dataclasses.replace(a, rope_theta=float(conf["rope_theta"])),
        param_dtype=traffic.get("param_dtype", "bfloat16"),
        compute_dtype=traffic.get("compute_dtype", "bfloat16"))
    return cfg


def check_device(chips: int, peaks: Dict):
    """The first device must be a TPU whose kind is in the peaks table, and
    there must be as many as the cell asks for.  Returns the devices."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise BenchError(f"no TPU: the first device is {d.platform!r}")
    if d.device_kind not in peaks:
        raise BenchError(f"device kind {d.device_kind!r} is not in "
                         f"peaks.json")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs[:chips]


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (persistent-cache
    loads included), and how many compilations, from its monitoring
    events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def lap(self):
        out = (self.seconds, self.compiles)
        self.seconds, self.compiles = 0.0, 0
        return out
