"""Configuration system for the repro framework.

Plain dataclasses (JSON-loadable via ``dacite``) describing models, training,
serving, meshes and input shapes.  Every assigned architecture registers a
``ModelConfig`` through :func:`register_arch`; launchers select them with
``--arch <id>``.
"""
from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

# ---------------------------------------------------------------------------
# Attention / FFN / family-specific blocks
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class RopeScaling:
    """YaRN context extension (``rope_scaling`` of type ``yarn``, as
    DeepSeek-V2 states it): rotary frequencies interpolated by a linear
    ramp between the correction dims of ``beta_fast`` and ``beta_slow`` at
    ``original_max_position_embeddings``, and the softmax scale multiplied
    by ``mscale(factor, mscale_all_dim)**2``."""

    factor: float = 40.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707


@dataclass(frozen=True)
class AttentionConfig:
    """Multi-head attention description (GQA or MLA)."""

    kind: str = "gqa"  # "gqa" | "mla"
    num_heads: int = 16
    num_kv_heads: int = 16
    head_dim: int = 64
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    rope_scaling: Optional[RopeScaling] = None
    # MLA (DeepSeek-V2) parameters; only read when kind == "mla".
    q_lora_rank: int = 0          # 0 => no query compression
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128

    @property
    def q_dim(self) -> int:
        if self.kind == "mla":
            return self.num_heads * (self.qk_nope_head_dim + self.qk_rope_head_dim)
        return self.num_heads * self.head_dim

    @property
    def kv_cache_dim_per_token(self) -> int:
        """Bytes-free cache width per token per layer (element count)."""
        if self.kind == "mla":
            # compressed kv latent + decoupled rope key
            return self.kv_lora_rank + self.qk_rope_head_dim
        return 2 * self.num_kv_heads * self.head_dim


@dataclass(frozen=True)
class MoEConfig:
    """Token-choice top-k mixture-of-experts FFN."""

    num_experts: int = 8
    num_experts_per_tok: int = 2
    num_shared_experts: int = 0
    d_ff_expert: int = 512          # hidden dim of each routed expert
    d_ff_shared: int = 0            # hidden dim of the shared expert(s)
    moe_every: int = 1              # MoE FFN every k-th layer (others dense)
    moe_offset: int = 0             # phase of the MoE layers within the period
    first_k_dense: int = 0          # first k layers use a dense FFN
    d_ff_dense: int = 0             # dense-FFN hidden dim for non-MoE layers
    router_dtype: str = "float32"
    router_noise: float = 0.0
    aux_loss_coef: float = 0.001
    capacity_factor: float = 1.25   # <=0: dropless, grouped by expert
    # renormalise the top-k gates to sum 1; False needs the dropless MoE
    norm_topk_prob: bool = True
    # experts [0, experts_held) live here (expert parallelism); 0 = all.
    # The router keeps all num_experts outputs.  Dropless only.
    experts_held: int = 0
    # "softmax": top-k of the softmax over the router's outputs.
    # "sigmoid": DeepSeek-V3's noaux_tc routing: sigmoid affinities, top-k
    # of affinity + a per-expert correction bias that no gradient reaches,
    # gates from the affinities.  Dropless only.
    scoring: str = "softmax"
    routed_scaling_factor: float = 1.0   # on the routed gates
    # sigmoid scoring: the step moves each bias by this rate towards
    # balance, b += rate * sign(mean load - load) (DeepSeek-V3's gamma)
    bias_update_rate: float = 0.0
    # dropless: the grouped matmuls visit at least this multiple of the
    # rows a layer's held experts expect, S*K*held/E (zero rows past those
    # routed, which add nothing), so that a step's work does not follow
    # its routing below that bound; 0 = the rows routed alone.  Where
    # these rows lie below half a layer's S*K, that half also bounds its
    # row-wide work (``layers.held_row_bound``)
    grouped_rows_floor: float = 0.0

    @property
    def held(self) -> int:
        return self.experts_held or self.num_experts


@dataclass(frozen=True)
class SSMConfig:
    """Mamba-style selective state-space block."""

    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0                # 0 => ceil(d_model / 16)

    def resolved_dt_rank(self, d_model: int) -> int:
        return self.dt_rank or -(-d_model // 16)


@dataclass(frozen=True)
class RWKVConfig:
    """RWKV-6 ("Finch") time-mix / channel-mix block."""

    head_dim: int = 64
    decay_lora: int = 64            # rank of the data-dependent decay LoRA
    mix_lora: int = 32              # rank of the token-shift mix LoRA
    gate_lora: int = 64


@dataclass(frozen=True)
class FrontendConfig:
    """Modality frontend STUB: precomputed embeddings fed to the backbone.

    ``input_specs`` produces ``(batch, num_prefix, d_model)`` embeddings; no
    vision/audio tower is instantiated (per assignment: backbone only).
    """

    kind: str = "none"              # "none" | "patch" (vlm) | "frames" (audio)
    num_prefix: int = 0             # prefix embeddings per example


# ---------------------------------------------------------------------------
# Convnet (DilatedVGG — the paper's own workload)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ConvLayerConfig:
    name: str
    kind: str                       # "conv" | "pool" | "dense" | "upsample"
    in_ch: int = 0
    out_ch: int = 0
    kernel: int = 3
    stride: int = 1
    dilation: int = 1
    # dense layers are 1x1 convs over the feature map in DilatedVGG-style nets


@dataclass(frozen=True)
class ConvNetConfig:
    layers: Tuple[ConvLayerConfig, ...] = ()
    in_hw: Tuple[int, int] = (1024, 2048)
    in_ch: int = 3
    num_classes: int = 19


# ---------------------------------------------------------------------------
# Top-level model config
# ---------------------------------------------------------------------------

FAMILIES = ("dense", "moe", "ssm", "hybrid", "encdec", "vlm", "audio", "convnet")


@dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"
    num_layers: int = 2
    d_model: int = 128
    d_ff: int = 512
    vocab_size: int = 512
    attention: Optional[AttentionConfig] = None
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    rwkv: Optional[RWKVConfig] = None
    frontend: Optional[FrontendConfig] = None
    convnet: Optional[ConvNetConfig] = None
    # hybrid (jamba): one attention layer every `attn_every` layers, rest SSM
    attn_every: int = 0
    # encoder-decoder
    encoder_layers: int = 0
    # misc
    act: str = "swiglu"             # "swiglu" | "gelu" | "relu2"
    norm: str = "rmsnorm"
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    logit_softcap: float = 0.0
    # multi-token prediction (DeepSeek-V3): 1 = one depth after the last
    # block predicting the token after next, its loss weighted by the coef
    mtp_depth: int = 0
    mtp_loss_coef: float = 0.0
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    max_seq_len: int = 4096

    # ---- derived quantities -------------------------------------------------
    def layer_kinds(self) -> List[str]:
        """Per-layer mixer kind for hybrid models: 'attn' or 'ssm'."""
        if self.family != "hybrid" or not self.attn_every:
            if self.family == "ssm" and self.rwkv is not None:
                return ["rwkv"] * self.num_layers
            if self.family == "ssm":
                return ["ssm"] * self.num_layers
            return ["attn"] * self.num_layers
        # Jamba: within each period of `attn_every`, exactly one attn layer
        # (at index attn_every//2, matching the released config).
        kinds = []
        for i in range(self.num_layers):
            kinds.append("attn" if i % self.attn_every == self.attn_every // 2 else "ssm")
        return kinds

    def ffn_kinds(self) -> List[str]:
        """Per-layer FFN kind: 'dense' or 'moe'."""
        if self.moe is None:
            return ["dense"] * self.num_layers
        kinds = []
        for i in range(self.num_layers):
            if i < self.moe.first_k_dense:
                kinds.append("dense")
            elif (i - self.moe.first_k_dense) % self.moe.moe_every \
                    == self.moe.moe_offset:
                kinds.append("moe")
            else:
                kinds.append("dense")
        return kinds

    def param_count(self) -> int:
        """Analytical parameter count (embedding + blocks + head)."""
        from repro.models import api  # local import to avoid cycles

        return api.param_count(self)

    def active_param_count(self) -> int:
        from repro.models import api

        return api.param_count(self, active_only=True)


# ---------------------------------------------------------------------------
# Input shapes (the assigned shape grid)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    mode: str                       # "train" | "prefill" | "decode"


LM_SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Train / serve / mesh configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adamw"
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    schedule: str = "cosine"        # "cosine" | "linear" | "constant"
    # distributed-optimization tricks
    grad_compression: str = "none"  # "none" | "int8_ef"
    grad_accum: int = 1


@dataclass(frozen=True)
class RematConfig:
    policy: str = "dots"            # "none" | "dots" | "full"


@dataclass(frozen=True)
class TrainConfig:
    seq_len: int = 4096
    global_batch: int = 256
    steps: int = 100
    seed: int = 0
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    remat: RematConfig = field(default_factory=RematConfig)
    checkpoint_every: int = 50
    checkpoint_dir: str = "/tmp/repro_ckpt"
    async_checkpoint: bool = True
    log_every: int = 10


@dataclass(frozen=True)
class ServeConfig:
    max_seq_len: int = 32_768
    max_batch: int = 128
    prefill_chunk: int = 1024
    kv_cache_dtype: str = "bfloat16"


@dataclass(frozen=True)
class MeshConfig:
    shape: Tuple[int, ...] = (16, 16)
    axes: Tuple[str, ...] = ("data", "model")

    @property
    def num_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


# ---------------------------------------------------------------------------
# Architecture registry
# ---------------------------------------------------------------------------

_ARCH_REGISTRY: Dict[str, Callable[[], "ArchSpec"]] = {}


@dataclass(frozen=True)
class ArchSpec:
    """One assigned architecture: full config + reduced smoke config + shapes."""

    arch_id: str
    model: ModelConfig
    smoke: ModelConfig
    shapes: Tuple[str, ...] = ("train_4k", "prefill_32k", "decode_32k", "long_500k")
    skip_shapes: Tuple[str, ...] = ()       # e.g. long_500k for full-attention
    skip_reason: str = ""
    source: str = ""

    def shape_cells(self) -> List[ShapeConfig]:
        return [LM_SHAPES[s] for s in self.shapes]


def register_arch(arch_id: str):
    def deco(fn: Callable[[], ArchSpec]):
        _ARCH_REGISTRY[arch_id] = fn
        return fn

    return deco


def get_arch(arch_id: str) -> ArchSpec:
    _ensure_configs_imported()
    if arch_id not in _ARCH_REGISTRY:
        raise KeyError(
            f"unknown arch {arch_id!r}; available: {sorted(_ARCH_REGISTRY)}"
        )
    return _ARCH_REGISTRY[arch_id]()


def list_archs() -> List[str]:
    _ensure_configs_imported()
    return sorted(_ARCH_REGISTRY)


def _ensure_configs_imported() -> None:
    # Importing repro.configs registers every architecture module.
    import repro.configs  # noqa: F401


# ---------------------------------------------------------------------------
# JSON round-trip helpers (system-description-file style configs)
# ---------------------------------------------------------------------------


def to_json(cfg: Any) -> str:
    return json.dumps(dataclasses.asdict(cfg), indent=2)


def from_json(cls, text: str):
    import dacite

    return dacite.from_dict(
        data_class=cls,
        data=json.loads(text),
        config=dacite.Config(cast=[tuple], strict=False),
    )
