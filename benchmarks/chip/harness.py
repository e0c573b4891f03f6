"""Finds a cell's files by name and holds what both loops share.

``BENCHMARK.json`` (at the checkout's root) names each cell's configuration
and traffic mix.  The files are found by those names alone:

* ``configs/<config>.json``: the configuration as it is run, with the cut
  it makes from its source (``program_config``);
* ``traffic/<mix>.json``: the mix's kind (``serve`` or ``train``) and its
  parameters;
* ``limits/<cell>.json``: the limits of the cell's correctness check;
* ``metrics/<metric>.py``: one reader per per-layer metric, with
  ``read(ctx) -> float | None``;
* ``references/<config>.py``: the configuration's plain float32 reference,
  with ``reference.py``'s interface (``RefConfig.from_file``,
  ``highest_mm``, ``fp8_mm``, ``train``, ``logits_at``, ``gaps_at``,
  ``argmax_at``); ``reference.py`` where there is none;
* ``counts/<config>.py``: the operations and bytes the configuration's
  steps need, with ``flops.py``'s public functions; ``flops.py`` where
  there is none.

A later cell, mix, metric or configuration is new files and new
``BENCHMARK.json`` entries; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib
import importlib.util
import json
import re
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
    here: Path = HERE           # the directory its files were found in


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
                end_to_end=e2e, per_layer=per_layer, here=here)


def cell_from_files(name: str, here: Path = HERE) -> Cell:
    """A cell that ``BENCHMARK.json`` does not list, named
    ``<config>.<mix>``, for calibration runs; it has no limits."""
    conf, mix = name.rsplit(".", 1)
    lim = here / "limits" / f"{name}.json"
    return Cell(name=name, chips=1,
                config=load_json(here / "configs" / f"{conf}.json"),
                traffic=load_json(here / "traffic" / f"{mix}.json"),
                limits=load_json(lim) if lim.exists() else {"limits": {}},
                end_to_end=[], per_layer=[], here=here)


@functools.lru_cache(maxsize=None)
def _load(path: Path, kind: str):
    """The module in ``path``, loaded once a process, so that the jitted
    functions of a reference compile once however often it is asked for."""
    stem = re.sub(r"\W", "_", path.stem)
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{stem}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, here: Path = HERE):
    path = here / "metrics" / f"{name}.py"
    if not path.exists():
        raise BenchError(f"no reader {path}")
    return _load(path, "metric").read


def _own_or(conf: Dict, kind: str, default: str, here: Path):
    path = here / kind / f"{conf['name']}.py"
    return _load(path, kind) if path.exists() else \
        importlib.import_module(default)


def reference_for(conf: Dict, here: Path = HERE):
    """The configuration's plain reference: ``references/<name>.py``, or
    ``reference.py`` where there is none."""
    return _own_or(conf, "references", "reference", here)


def counts_for(conf: Dict, here: Path = HERE):
    """The configuration's operation and byte counts: ``counts/<name>.py``,
    or ``flops.py`` where there is none."""
    return _own_or(conf, "counts", "flops", here)


def _source(conf: Dict):
    """The source's value of a key: under ``published`` for a key the file
    lists in ``reduced``, else the file's own."""
    red, pub = conf.get("reduced", []), conf.get("published", {})
    missing = [k for k in red if k not in pub]
    if missing:
        raise BenchError(f"{conf['name']}.json reduces {missing} but gives "
                         f"no published value for them")
    return lambda k: pub[k] if k in red else conf.get(k)


def _widths(cfg, conf: Dict) -> Dict:
    """The registered arch's sizes under the keys of the configuration
    file, whose names follow its source's config.json."""
    a, moe = cfg.attention, cfg.moe
    w = {"hidden_size": cfg.d_model, "num_hidden_layers": cfg.num_layers,
         "num_attention_heads": a.num_heads,
         "num_key_value_heads": a.num_kv_heads,
         "vocab_size": cfg.vocab_size, "attention_bias": a.qkv_bias,
         "tie_word_embeddings": cfg.tie_embeddings}
    if a.kind == "mla":
        w.update(q_lora_rank=a.q_lora_rank or None,
                 kv_lora_rank=a.kv_lora_rank,
                 qk_nope_head_dim=a.qk_nope_head_dim,
                 qk_rope_head_dim=a.qk_rope_head_dim,
                 v_head_dim=a.v_head_dim)
    else:
        w["head_dim"] = a.head_dim
    if moe is None:
        w["intermediate_size"] = cfg.d_ff
    elif "n_routed_experts" in conf:            # DeepSeek's names
        w.update(n_routed_experts=moe.num_experts,
                 num_experts_per_tok=moe.num_experts_per_tok,
                 n_shared_experts=moe.num_shared_experts,
                 moe_intermediate_size=moe.d_ff_expert,
                 first_k_dense_replace=moe.first_k_dense,
                 intermediate_size=moe.d_ff_dense or cfg.d_ff)
    else:                                       # Mixtral's and Granite's
        w.update(num_local_experts=moe.num_experts,
                 num_experts_per_tok=moe.num_experts_per_tok,
                 intermediate_size=moe.d_ff_expert)
    return w


def _check_floors(conf: Dict):
    """The model-configs guide's floors on a cut (its section 4): at least
    four layers after the leading dense ones, 8 routed experts, an eighth
    of the vocabulary."""
    red, name = set(conf.get("reduced", [])), conf["name"]
    if "num_hidden_layers" in red and conf["num_hidden_layers"] \
            - conf.get("first_k_dense_replace", 0) < 4:
        raise BenchError(f"{name}.json keeps fewer than 4 layers after the "
                         f"leading dense ones")
    for k in ("num_local_experts", "n_routed_experts"):
        if k in red and conf[k] < 8:
            raise BenchError(f"{name}.json keeps {conf[k]} routed experts, "
                             f"fewer than 8")
    if "vocab_size" in red and \
            8 * conf["vocab_size"] < conf["published"]["vocab_size"]:
        raise BenchError(f"{name}.json keeps less than an eighth of the "
                         f"vocabulary")


def _set_field(obj, path: List[str], value, dotted: str):
    name = path[0]
    if not dataclasses.is_dataclass(obj) or \
            name not in {f.name for f in dataclasses.fields(obj)}:
        raise BenchError(f"the program's ModelConfig has no field {dotted!r}")
    if len(path) > 1:
        value = _set_field(getattr(obj, name), path[1:], value, dotted)
    return dataclasses.replace(obj, **{name: value})


def program_config(conf: Dict, traffic: Dict):
    """The program's ModelConfig for a configuration file.

    Every width the registered arch has is checked against the file's
    source value (``published`` for a key in ``reduced``).  Then the
    file's ``program`` object makes the cut: each entry names a ModelConfig
    field (dotted for a nested one, ``moe.num_experts``) and the file key,
    listed in ``reduced`` or ``assumed``, whose value it takes.  Besides
    that, only the published rope theta and norm epsilon and the dtypes
    the mix states differ from the registered arch."""
    from repro.core.config import get_arch

    spec = get_arch(conf["arch"])
    cfg = spec.smoke if conf.get("size") == "smoke" else spec.model
    source = _source(conf)
    want = _widths(cfg, conf)
    bad = {k: (v, source(k)) for k, v in want.items() if source(k) != v}
    moe = cfg.moe
    if not bad and moe is not None and moe.num_shared_experts and \
            moe.d_ff_shared != moe.num_shared_experts * moe.d_ff_expert:
        bad["shared experts' width"] = (moe.d_ff_shared, moe.num_shared_experts
                                        * moe.d_ff_expert)
    if bad:
        raise BenchError(f"registered {conf['arch']} differs from "
                         f"{conf['name']}.json: {bad}")
    _check_floors(conf)
    program = conf.get("program", {})
    answers = set(conf.get("reduced", [])) | set(conf.get("assumed", {}))
    for field, key in program.items():
        if key not in answers or key not in conf:
            raise BenchError(f"program field {field!r} takes {key!r}, which "
                             f"{conf['name']}.json does not list in reduced "
                             f"or assumed with a value")
        cfg = _set_field(cfg, field.split("."), conf[key], field)
    untaken = [k for k in conf.get("reduced", [])
               if k in want and k not in program.values()]
    if untaken:
        raise BenchError(f"{conf['name']}.json reduces {untaken} but no "
                         f"program field takes them")
    cfg = dataclasses.replace(
        cfg, norm_eps=float(conf["rms_norm_eps"]),
        attention=dataclasses.replace(cfg.attention,
                                      rope_theta=float(conf["rope_theta"])),
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
