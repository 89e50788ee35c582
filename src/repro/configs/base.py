"""Config system for the HetSeq-JAX framework.

Every assigned architecture is expressed as a :class:`ModelConfig`; shapes
(train_4k / prefill_32k / decode_32k / long_500k) are :class:`ShapeConfig`;
the heterogeneous-capacity training setup (the paper's contribution) is a
:class:`HetConfig`.  ``resolve(arch_id)`` returns the registered full config,
``smoke_config(arch_id)`` a reduced same-family config for CPU smoke tests.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field, replace
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

# --------------------------------------------------------------------------
# Model configuration
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts settings (GShard-style top-k routing)."""

    num_experts: int = 0
    top_k: int = 2
    expert_d_ff: int = 0            # FFN hidden size inside each expert
    num_shared_experts: int = 0     # DeepSeek-style always-on experts
    shared_d_ff: int = 0            # hidden size of the shared expert(s)
    dense_residual: bool = False    # Arctic-style parallel dense FFN branch
    capacity_factor: float = 1.25   # per-device expert capacity multiplier
    capacity_factor_eval: float = 2.0  # prefill/eval: generous, fewer drops
    router_jitter: float = 0.0
    aux_loss_coef: float = 0.01     # load-balancing auxiliary loss

    @property
    def enabled(self) -> bool:
        return self.num_experts > 0


@dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-V2 multi-head latent attention settings."""

    kv_lora_rank: int = 0           # latent dim for compressed KV
    q_lora_rank: int = 0            # 0 => dense q projection
    rope_head_dim: int = 64         # decoupled RoPE dims (shared across heads)
    nope_head_dim: int = 128        # per-head non-RoPE dims
    v_head_dim: int = 128

    @property
    def enabled(self) -> bool:
        return self.kv_lora_rank > 0


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 (SSD) settings for hybrid / ssm architectures."""

    state_dim: int = 0              # N: per-head SSM state size (0 => off)
    head_dim: int = 64              # P: channels per SSM head
    num_heads: int = 0              # 0 => derived from d_inner / head_dim
    expand: int = 2                 # d_inner = expand * d_model
    conv_kernel: int = 4
    chunk_size: int = 256           # SSD chunked-scan block length
    ngroups: int = 1                # B/C groups (GVA-style)

    @property
    def enabled(self) -> bool:
        return self.state_dim > 0


@dataclass(frozen=True)
class XLSTMConfig:
    """xLSTM block-stack settings (alternating mLSTM / sLSTM blocks)."""

    enabled: bool = False
    num_heads: int = 4
    slstm_every: int = 2            # every k-th block is an sLSTM block
    proj_factor_mlstm: float = 2.0  # mLSTM up-projection factor
    proj_factor_slstm: float = 1.333  # post-sLSTM gated FFN factor
    conv_kernel: int = 4


@dataclass(frozen=True)
class HybridConfig:
    """Zamba2-style hybrid: Mamba2 backbone + weight-shared attention block."""

    enabled: bool = False
    attn_every: int = 6             # shared attention applied every k layers
    shared_attn_d_ff: int = 0       # FFN inside the shared block (0 = none)


@dataclass(frozen=True)
class ModelConfig:
    """A decoder-only backbone configuration (LM family)."""

    name: str = "unnamed"
    family: str = "dense"           # dense | moe | hybrid | ssm | vlm | audio
    num_layers: int = 12
    d_model: int = 768
    num_heads: int = 12
    num_kv_heads: int = 12
    d_ff: int = 3072                # dense FFN hidden (0 => no FFN sub-block)
    vocab_size: int = 50304
    head_dim: int = 0               # 0 => d_model // num_heads
    max_seq_len: int = 4096

    norm: str = "rmsnorm"           # rmsnorm | layernorm | nonparam_ln
    activation: str = "swiglu"      # swiglu | gelu | geglu
    rope_theta: float = 10000.0
    qk_norm: bool = False           # Chameleon-style query/key norm
    tie_embeddings: bool = False
    logit_softcap: float = 0.0

    moe: MoEConfig = field(default_factory=MoEConfig)
    mla: MLAConfig = field(default_factory=MLAConfig)
    ssm: SSMConfig = field(default_factory=SSMConfig)
    xlstm: XLSTMConfig = field(default_factory=XLSTMConfig)
    hybrid: HybridConfig = field(default_factory=HybridConfig)

    # modality frontend stubs ([vlm]/[audio]): input_specs() provides
    # precomputed frame/patch embeddings instead of token ids.
    frontend: str = "token"         # token | embedding_stub

    # numerics / memory policy
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    remat: str = "full"             # none | dots | full
    scan_layers: bool = True
    attention_impl: str = "reference"   # see ATTENTION_IMPLS

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        if self.attention_impl not in ATTENTION_IMPLS:
            raise ValueError(
                f"attention_impl must be one of {ATTENTION_IMPLS}, got "
                f"'{self.attention_impl}'")

    # ---- derived quantities -------------------------------------------------
    @property
    def q_per_kv(self) -> int:
        return self.num_heads // max(self.num_kv_heads, 1)

    def param_count(self) -> int:
        """Total parameter count (analytic, matches init_params)."""
        from repro.models.model import count_params_analytic

        return count_params_analytic(self)

    def active_param_count(self) -> int:
        """Parameters active per token (MoE counts top_k + shared experts)."""
        from repro.models.model import count_params_analytic

        return count_params_analytic(self, active_only=True)

    @property
    def sub_quadratic(self) -> bool:
        """True if long-context decode is supported (SSM/hybrid families)."""
        return self.ssm.enabled or self.xlstm.enabled


# --------------------------------------------------------------------------
# Shapes (assigned input-shape set for the LM family)
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                       # train | prefill | decode

    @property
    def tokens(self) -> int:
        return self.seq_len * self.global_batch


TRAIN_4K = ShapeConfig("train_4k", 4096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524288, 1, "decode")

SHAPES: Dict[str, ShapeConfig] = {
    s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)
}


def shape_applicable(model: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Whether a (arch x shape) cell runs, and why not if skipped."""
    if shape.name == "long_500k" and not model.sub_quadratic:
        return False, (
            "long_500k requires sub-quadratic attention; "
            f"{model.name} is pure full-attention"
        )
    return True, ""


# --------------------------------------------------------------------------
# Heterogeneous-capacity (the paper's technique) configuration
# --------------------------------------------------------------------------


# Accepted values of the HetConfig mode fields. These constants are the
# single source of truth: HetConfig.validate() checks membership,
# launch/steps.py derives its config checks from them, launch/train.py
# exposes them as CLI choices, and tests/test_config_docs.py asserts the
# README config matrix agrees with them.
GRAD_REDUCTION_MODES = ("allreduce", "bucketed_allreduce", "hierarchical")
OVERLAP_MODES = ("none", "buckets", "backward")
COMPRESSION_MODES = ("none", "int8")
QUANTIZE_IMPLS = ("reference", "pallas")
# ModelConfig.attention_impl: selects the attention kernels on BOTH hot
# paths — train/prefill flash attention (models/blocks.py) and the paged
# decode kernels on the serving path (models/kvcache.py). "reference" is
# the portable jnp path ("dense" forces the full-score-matrix oracle);
# "pallas" selects the fused TPU kernels, falling back LOUDLY to
# interpret mode where the backend can't compile Pallas
# (compat.pallas_interpret_fallback).
ATTENTION_IMPLS = ("reference", "dense", "pallas")
WEIGHTING_MODES = ("tokens", "samples", "canonical")
PIPELINE_MODES = ("1f1b", "gpipe")

# Which grad_reduction modes the overlap pipelines schedule: overlap is
# a schedule OF the explicit bucketed engine, so it needs one of these
# plus bucket_mb > 0.
EXPLICIT_REDUCTIONS = ("bucketed_allreduce", "hierarchical")


@dataclass(frozen=True)
class HetConfig:
    """HetSeq heterogeneous data-parallel settings.

    Fields (one line each — valid values and interactions; see
    docs/architecture.md for the full narrative):

    ``capacities``: relative throughput per DP rank (pod x data
        position); empty tuple = homogeneous. The capacity planner
        turns these into per-rank real-row counts, remaining buffer
        rows are weight-0 dummies (paper M1/M3).
    ``weighting``: "tokens" | "samples" — what a unit of loss weight
        counts (paper M3 aggregation contract) — or "canonical": the
        order-canonical executor (core/weighting.py) — per-row vmapped
        gradients summed in global-row order with one fixed reduction
        tree, so the step is bit-identical across capacity replans;
        costs per-row grads and requires grad_reduction="allreduce",
        overlap="none", compression="none", accum_steps=1.
    ``grad_reduction``: "allreduce" (paper-faithful, XLA-automatic) |
        "bucketed_allreduce" (explicit flat-buffer reduction over the
        DP axes; requires ``bucket_mb > 0``) | "hierarchical" (in-pod
        automatic over ICI, cross-pod DCN leg explicit, optionally
        compressed; bucketed when ``bucket_mb > 0``).
    ``compression``: "none" | "int8" — cross-pod payload encoding;
        only consulted by "hierarchical" (other modes reduce fp32).
    ``error_feedback``: keep per-rank residuals of the int8 quantizer
        (both stages) and fold them into the next step; only active for
        hierarchical + int8 on a multi-pod mesh.
    ``bucket_mb``: bucket payload in MiB of f32 for the bucketed
        engine (PyTorch-DDP-style knob); 0 keeps the legacy per-leaf
        walk and is invalid with "bucketed_allreduce" or any overlap.
    ``quantize_impl``: "reference" (pure jnp, portable) | "pallas"
        (fused TPU kernels) for the int8 exchange kernels.
    ``overlap``: "none" (monolithic: pack -> 2 collectives -> unpack
        -> tree-wide update) | "buckets" (double-buffered per-bucket
        pipeline fused with flat-view optimizer updates, after the
        backward pass) | "backward" (beyond "buckets": buckets flush
        DURING backprop as their last contributing layer's cotangent
        lands; requires ``ModelConfig.scan_layers=False`` and a
        uniform-stack architecture). Both pipelines require an
        explicit ``grad_reduction`` and ``bucket_mb > 0``; global-norm
        clipping and LAMB keep the pipelined exchange but update
        behind a barrier.
    ``accum_steps``: gradient-accumulation microbatch count (paper M4
        delayed update); >= 1. With ``pipeline_stages > 1`` the
        microbatches ARE the pipeline's 1F1B stream, so
        ``accum_steps >= pipeline_stages`` (the pipe must fill).
    ``straggler_ema``: EMA decay of per-rank step-time tracking in
        [0, 1) (core/straggler.py).
    ``replan_interval``: steps between soft capacity replans; >= 1.
    ``pipeline_stages``: contiguous layer-stack stages (core/pipeline.py
        StagePlan, sized by per-pod capacity scores); 1 = no pipelining.
        > 1 requires a uniform-stack architecture with
        ``scan_layers=False`` (checked at build time), overlap="none"
        (the overlap pipelines flush buckets over the DP axes
        mid-backward, which cannot cross a stage boundary),
        weighting != "canonical", and grad_reduction "allreduce" or
        "bucketed_allreduce".
    ``pipeline_schedule``: "1f1b" (warmup / steady 1F1B / drain) |
        "gpipe" (all forwards then all backwards); see PIPELINE_MODES.
    """

    capacities: Tuple[float, ...] = ()      # empty => homogeneous
    weighting: str = "tokens"               # tokens | samples
    grad_reduction: str = "allreduce"       # see GRAD_REDUCTION_MODES
    compression: str = "none"               # see COMPRESSION_MODES
    error_feedback: bool = True
    bucket_mb: float = 0.0                  # >0 => bucketed flat-buffer engine
    quantize_impl: str = "reference"        # see QUANTIZE_IMPLS
    overlap: str = "none"                   # see OVERLAP_MODES
    accum_steps: int = 1                    # delayed update (paper M4)
    straggler_ema: float = 0.9
    replan_interval: int = 100              # steps between capacity replans
    pipeline_stages: int = 1                # >1 => pipelined layer stack
    pipeline_schedule: str = "1f1b"         # see PIPELINE_MODES

    def validate(self) -> "HetConfig":
        """Mesh-independent config validation. Raises ``ValueError``
        with an actionable message instead of failing deep in the
        pipeline; mesh/model-dependent checks (reduction axes, stack
        plan, scan_layers) live in ``launch/steps.py`` and run at
        ``build_train_step`` time. Returns self for chaining."""
        def member(name, value, allowed):
            if value not in allowed:
                raise ValueError(
                    f"HetConfig.{name}='{value}' is not one of "
                    f"{' | '.join(allowed)}")

        member("weighting", self.weighting, WEIGHTING_MODES)
        member("grad_reduction", self.grad_reduction, GRAD_REDUCTION_MODES)
        member("compression", self.compression, COMPRESSION_MODES)
        member("quantize_impl", self.quantize_impl, QUANTIZE_IMPLS)
        member("overlap", self.overlap, OVERLAP_MODES)
        member("pipeline_schedule", self.pipeline_schedule, PIPELINE_MODES)
        if self.pipeline_stages < 1:
            raise ValueError(
                f"HetConfig.pipeline_stages must be >= 1, got "
                f"{self.pipeline_stages}")
        if self.bucket_mb < 0:
            raise ValueError(
                f"HetConfig.bucket_mb must be >= 0, got {self.bucket_mb}")
        if self.accum_steps < 1:
            raise ValueError(
                f"HetConfig.accum_steps must be >= 1, got "
                f"{self.accum_steps}")
        if not 0.0 <= self.straggler_ema < 1.0:
            raise ValueError(
                f"HetConfig.straggler_ema must be in [0, 1), got "
                f"{self.straggler_ema}")
        if self.replan_interval < 1:
            raise ValueError(
                f"HetConfig.replan_interval must be >= 1, got "
                f"{self.replan_interval}")
        if any(c < 0 for c in self.capacities):
            raise ValueError(
                f"HetConfig.capacities must be non-negative, got "
                f"{self.capacities}")
        if self.grad_reduction == "bucketed_allreduce" \
                and self.bucket_mb <= 0:
            raise ValueError(
                "HetConfig.grad_reduction='bucketed_allreduce' needs "
                "bucket_mb > 0 (the explicit flat-buffer engine)")
        if self.overlap != "none":
            if self.grad_reduction not in EXPLICIT_REDUCTIONS:
                raise ValueError(
                    f"HetConfig.overlap='{self.overlap}' needs an "
                    f"explicit reduction "
                    f"({' | '.join(EXPLICIT_REDUCTIONS)}), not "
                    f"'{self.grad_reduction}'")
            if self.bucket_mb <= 0:
                raise ValueError(
                    f"HetConfig.overlap='{self.overlap}' needs "
                    f"bucket_mb > 0 (a bucket grid to pipeline over)")
        if self.weighting == "canonical":
            # one fixed reduction tree over global rows — any engine
            # that regroups the sum (buckets, hierarchy, compression,
            # accumulation) would break the bit-identity guarantee
            for field, value, want in (
                    ("grad_reduction", self.grad_reduction, "allreduce"),
                    ("overlap", self.overlap, "none"),
                    ("compression", self.compression, "none")):
                if value != want:
                    raise ValueError(
                        f"HetConfig.weighting='canonical' requires "
                        f"{field}='{want}', got '{value}' (the "
                        f"order-canonical sum must be the only "
                        f"reduction)")
            if self.accum_steps != 1:
                raise ValueError(
                    "HetConfig.weighting='canonical' requires "
                    f"accum_steps=1, got {self.accum_steps}")
        if self.pipeline_stages > 1:
            if self.overlap != "none":
                raise ValueError(
                    f"HetConfig.overlap='{self.overlap}' is incompatible "
                    f"with pipeline_stages={self.pipeline_stages}: the "
                    "overlap pipelines flush grad buckets over the DP "
                    "axes mid-backward, which cannot cross a pipeline "
                    "stage boundary (each stage owns only its layer "
                    "slice); use overlap='none' — the pipeline step "
                    "already reduces grads per-stage")
            if self.weighting == "canonical":
                raise ValueError(
                    "HetConfig.weighting='canonical' is incompatible "
                    f"with pipeline_stages={self.pipeline_stages}: the "
                    "order-canonical executor needs one fixed "
                    "whole-model reduction tree, but 1F1B regroups the "
                    "sum per (stage, microbatch)")
            if self.grad_reduction == "hierarchical":
                raise ValueError(
                    "HetConfig.grad_reduction='hierarchical' is not "
                    f"supported with pipeline_stages="
                    f"{self.pipeline_stages}; use 'allreduce' or "
                    "'bucketed_allreduce' (per-stage bucket flush)")
            if self.accum_steps < self.pipeline_stages:
                raise ValueError(
                    f"HetConfig.pipeline_stages={self.pipeline_stages} "
                    f"needs accum_steps >= pipeline_stages (got "
                    f"{self.accum_steps}): the accumulation microbatches "
                    "are the 1F1B stream and the pipe must fill")
        return self


@dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adamw"
    lr: float = 3e-4
    betas: Tuple[float, float] = (0.9, 0.98)    # paper: transformer betas
    eps: float = 1e-9
    weight_decay: float = 0.01
    grad_clip: float = 1.0
    schedule: str = "inverse_sqrt"              # inverse_sqrt | linear | cosine | constant
    warmup_steps: int = 4000
    total_steps: int = 100000
    m_dtype: str = "float32"
    v_dtype: str = "float32"


@dataclass(frozen=True)
class MeshConfig:
    """Logical mesh. DP spans (pod, data); TP/EP/SP use model."""

    shape: Tuple[int, ...] = (16, 16)
    axes: Tuple[str, ...] = ("data", "model")

    @property
    def multi_pod(self) -> bool:
        return "pod" in self.axes

    @property
    def num_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n

    @property
    def dp_size(self) -> int:
        n = 1
        for ax, s in zip(self.axes, self.shape):
            if ax in ("pod", "data"):
                n *= s
        return n

    @property
    def model_size(self) -> int:
        for ax, s in zip(self.axes, self.shape):
            if ax == "model":
                return s
        return 1


@dataclass(frozen=True)
class TrainConfig:
    """One full training-run configuration.

    Fields (one line each):

    ``model``: the :class:`ModelConfig` backbone being trained.
    ``shape``: the (seq_len, global_batch) training cell; kind "train".
    ``het``: the :class:`HetConfig` heterogeneous-DP settings — run
        ``het.validate()`` / ``build_train_step`` for the interaction
        rules (overlap needs bucket_mb > 0, "backward" additionally
        needs ``model.scan_layers=False`` and a uniform stack, ...).
    ``optimizer``: :class:`OptimizerConfig`; name "adamw" | "lamb"
        (LAMB and ``grad_clip > 0`` force the overlap pipelines onto
        the barrier update path).
    ``mesh``: logical mesh description; DP spans (pod, data), TP uses
        "model".
    ``seed``: global RNG seed — one key IS the broadcast (paper M8).
    ``zero1``: shard optimizer state over DP like params (beyond
        paper); ignored by the overlap modes (packed moments are
        replicated over the reduction axes).
    ``label_smoothing``: CE label smoothing in [0, 1); the paper's
        translation task uses 0.1.
    ``log_every``: steps between progress log lines; >= 1.
    ``ckpt_every``: steps between checkpoints; 0 disables periodic
        saves (a final save still happens in the driver).
    ``ckpt_dir``: checkpoint directory (versioned step_<N> subdirs).
    ``ckpt_keep``: checkpoints retained by rotation; 0 keeps all.
    """

    model: ModelConfig
    shape: ShapeConfig = TRAIN_4K
    het: HetConfig = field(default_factory=HetConfig)
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    seed: int = 0
    zero1: bool = True              # shard optimizer state over DP (beyond paper)
    label_smoothing: float = 0.0    # paper translation task uses 0.1
    log_every: int = 10
    ckpt_every: int = 1000
    ckpt_dir: str = "/tmp/hetseq_ckpt"
    ckpt_keep: int = 3


def accum_for(model: ModelConfig, multi_pod: bool = False) -> int:
    """Per-arch gradient-accumulation (paper M4, delayed update) policy
    for the production train_4k cell.

    Activation temps scale with per-microbatch tokens; the large-d /
    MoE-giant cells need accumulation to fit 16 GB HBM per chip. The
    microbatch must still give every DP rank >= 1 row:
    256 rows / 32 ranks (multi-pod) caps accum at 8 there.

    NOTE: the CPU dry-run backend legalizes bf16 GEMMs to f32 (operand
    copies), inflating measured activation memory ~2x vs real TPU; the
    accum chosen here fits even that pessimistic bound.
    """
    policy = {
        "chameleon-34b": (4, 4),         # (single-pod, multi-pod)
        "glm4-9b": (2, 2),
        "phi4-mini-3.8b": (2, 2),
        "deepseek-v2-236b": (8, 8),
        "arctic-480b": (16, 8),
        "zamba2-2.7b": (2, 2),
        "musicgen-large": (2, 2),
    }.get(model.name, (1, 1))
    return policy[1 if multi_pod else 0]


def optimizer_for(model: ModelConfig, **overrides) -> OptimizerConfig:
    """Per-architecture optimizer-state dtype policy.

    The two MoE giants cannot hold fp32 Adam moments on 256 x 16 GB:
      arctic-480b      : 480e9 x 12 B (fp32 p+m+v) / 256 = 22.5 GB/chip.
                         bf16 p+m+v => 11.25 GB/chip
                         (stochastic-rounding-free bf16 m/v is the
                         standard large-MoE compromise).
      deepseek-v2-236b : fp32 params + bf16 m/v => 7.4 GB/chip.
    Everything else keeps full fp32 state.
    """
    policy = {
        "arctic-480b": {"m_dtype": "bfloat16", "v_dtype": "bfloat16"},
        "deepseek-v2-236b": {"m_dtype": "bfloat16", "v_dtype": "bfloat16"},
    }.get(model.name, {})
    policy.update(overrides)
    return OptimizerConfig(**policy)


# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------

_REGISTRY: Dict[str, Callable[[], ModelConfig]] = {}
_SMOKE: Dict[str, Callable[[], ModelConfig]] = {}


def register(arch_id: str, full: Callable[[], ModelConfig],
             smoke: Callable[[], ModelConfig]) -> None:
    _REGISTRY[arch_id] = full
    _SMOKE[arch_id] = smoke


def resolve(arch_id: str) -> ModelConfig:
    _ensure_loaded()
    if arch_id not in _REGISTRY:
        raise KeyError(f"unknown arch '{arch_id}'; known: {sorted(_REGISTRY)}")
    return _REGISTRY[arch_id]()


def smoke_config(arch_id: str) -> ModelConfig:
    _ensure_loaded()
    return _SMOKE[arch_id]()


def list_archs() -> List[str]:
    _ensure_loaded()
    return sorted(_REGISTRY)


_LOADED = False


def _ensure_loaded() -> None:
    global _LOADED
    if _LOADED:
        return
    # import all arch config modules for their register() side effects
    from repro.configs import (  # noqa: F401
        olmo_1b, tinyllama_1_1b, glm4_9b, phi4_mini_3_8b, chameleon_34b,
        arctic_480b, deepseek_v2_236b, zamba2_2_7b, musicgen_large, xlstm_125m,
    )
    _LOADED = True
