"""Model / shape / run configuration for iDDS-JAX.

Every assigned architecture is expressed as a ``ModelConfig``; the four
assigned input shapes are ``ShapeConfig`` entries.  Configs are plain
dataclasses so they serialize trivially (the iDDS client/server boundary
round-trips them through JSON).
"""
from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass
from typing import Any, Dict, List, Tuple

# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------


@dataclass
class ModelConfig:
    name: str
    family: str  # dense | ssm | hybrid | moe | encdec | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // num_heads

    # --- attention details ---
    qkv_bias: bool = False
    mlp_bias: bool = False
    gated_mlp: bool = True  # SwiGLU-style (llama family); False -> plain MLP
    act: str = "silu"  # silu | gelu (exact, erf) | gelu_tanh
    rope_theta: float = 10_000.0
    sliding_window: int = 0  # 0 = full attention

    # --- MoE ---
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_capacity_factor: float = 1.25

    # --- SSM (Mamba2 / SSD) ---
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_chunk: int = 128
    ssm_conv: int = 4
    ssm_groups: int = 1  # B/C groups (mamba_ngroups); heads split evenly

    # --- hybrid (Zamba2): before each Mamba2 layer of `hybrid_layer_ids`
    # one of `num_mem_blocks` shared transformer blocks (used in turn) runs
    # over [hidden, original embeddings]; its output, through a
    # per-application linear map, joins that layer's input ---
    hybrid_layer_ids: Tuple[int, ...] = ()
    num_mem_blocks: int = 1
    adapter_rank: int = 0  # per-application LoRA on the MLP's gate/up; 0 none
    attn_input_width: int = 0  # 0 -> d_model
    attn_scale: float = 0.0  # softmax scale; 0 -> head_dim ** -0.5

    # --- encoder-decoder (whisper) ---
    encoder_layers: int = 0
    encoder_frames: int = 1500  # fixed mel-frame count after conv frontend

    # --- VLM (llava): anyres patch embeddings prepended to the sequence ---
    num_img_patches: int = 0

    # --- numerics ---
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    tie_embeddings: bool = False

    # --- serving ---
    kv_cache_dtype: str = "bfloat16"  # bfloat16 | int8

    # --- provenance ---
    source: str = ""

    def __post_init__(self) -> None:
        if self.head_dim == 0 and self.num_heads:
            self.head_dim = self.d_model // self.num_heads
        self.hybrid_layer_ids = tuple(self.hybrid_layer_ids)
        if self.family == "ssm":
            self.hybrid_layer_ids = ()

    # Derived quantities -----------------------------------------------------
    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def attn_in_dim(self) -> int:
        return self.attn_input_width or self.d_model

    @property
    def ssm_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.ssm_inner // self.ssm_head_dim

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), sort_keys=True)

    @classmethod
    def from_json(cls, s: str) -> "ModelConfig":
        return cls(**json.loads(s))

    def replace(self, **kw: Any) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Shape configuration (assigned input shapes)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Run configuration (training hyperparameters, parallelism knobs)
# ---------------------------------------------------------------------------


@dataclass
class RunConfig:
    """Knobs for a concrete (arch x shape x mesh) lowering/run."""

    accum_steps: int = 1  # gradient-accumulation microbatches
    remat: str = "full"  # none | full | dots
    scan_layers: bool = True
    use_pallas: bool = False  # CPU dry-run/smoke uses the XLA ref path
    grad_compression: str = "none"  # none | bf16
    learning_rate: float = 3e-4
    weight_decay: float = 0.1
    warmup_steps: int = 100
    total_steps: int = 1_000
    max_grad_norm: float = 1.0
    seed: int = 0
    attn_block_k: int = 512  # flash-style chunk for the XLA ref path
    attn_block_q: int = 0  # 0 = no q chunking
    ce_mode: str = "blockwise"  # blockwise (custom-VJP, O(T*D) mem) | direct
    ce_block_v: int = 8192
    ce_dtype: str = "bfloat16"  # logits matmul input dtype (f32 accum)
    moe_impl: str = "shardmap"  # shardmap (explicit EP) | gspmd (auto)
    flash_custom_vjp: bool = True  # False = autodiff through the scan
    #   (baseline: stacks per-block score residuals, O(S^2) memory)
    logits_in_fp32: bool = True
    # §Perf levers
    fuse_qkv: bool = True
    opt_state_dtype: str = "float32"  # float32 | bfloat16 (compression)

    def replace(self, **kw: Any) -> "RunConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, ModelConfig] = {}
_SMOKE_REGISTRY: Dict[str, ModelConfig] = {}


def register(cfg: ModelConfig, smoke: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    _SMOKE_REGISTRY[cfg.name] = smoke
    return cfg


def get_config(name: str) -> ModelConfig:
    _ensure_loaded()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def get_smoke_config(name: str) -> ModelConfig:
    _ensure_loaded()
    return _SMOKE_REGISTRY[name]


def list_archs() -> List[str]:
    _ensure_loaded()
    return sorted(_REGISTRY)


_LOADED = False


def _ensure_loaded() -> None:
    global _LOADED
    if _LOADED:
        return
    _LOADED = True
    # importing the arch modules populates the registry
    from repro.configs import archs  # noqa: F401


# Which (arch, shape) cells are runnable; the rest are documented skips.
PURE_ATTENTION_FAMILIES = ("dense", "moe", "encdec", "vlm")


def cell_is_runnable(cfg: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Return (runnable, reason-if-skipped) for an (arch x shape) cell."""
    if shape.name == "long_500k" and cfg.family in PURE_ATTENTION_FAMILIES:
        return False, (
            "long_500k requires sub-quadratic attention / bounded state; "
            f"{cfg.name} is pure full-attention (see DESIGN.md skip list)"
        )
    return True, ""


def all_cells() -> List[Tuple[str, str, bool, str]]:
    """Every (arch, shape) pair with runnability flag + skip reason."""
    _ensure_loaded()
    out = []
    for arch in list_archs():
        cfg = get_config(arch)
        for sname, shape in SHAPES.items():
            ok, why = cell_is_runnable(cfg, shape)
            out.append((arch, sname, ok, why))
    return out
