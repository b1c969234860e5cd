"""Config dataclasses for the framework.

A single ``ModelConfig`` describes every architecture family in the assigned
pool (dense / MoE / SSM / hybrid / VLM / audio) plus the paper's own
encoder-decoder MT model.  ``DecodeConfig`` carries the blockwise-parallel-
decoding (BPD) parameters from the paper; ``TrainConfig`` the optimizer/loop
parameters.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16}


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN RoPE scaling (arXiv:2309.00071) as DeepSeek-V2 configures it
    (its ``rope_scaling`` with ``type`` "yarn")."""

    factor: float = 1.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    # --- identity -----------------------------------------------------------
    name: str = "model"
    family: str = "dense"          # dense | moe | ssm | hybrid | vlm | audio | seq2seq
    source: str = ""               # citation for the config numbers

    # --- trunk shape ---------------------------------------------------------
    num_layers: int = 2
    d_model: int = 256
    num_heads: int = 4             # query heads (ignored for attn-free blocks)
    num_kv_heads: int = 4          # GQA kv heads
    head_dim: int = 0              # 0 -> d_model // num_heads
    d_ff: int = 1024               # dense MLP width (per-expert width for MoE)
    vocab_size: int = 512

    # --- block composition ---------------------------------------------------
    block_type: str = "attn"       # attn | rwkv6 | hymba
    mlp_type: str = "dense"        # dense | moe | rwkv_channel_mix
    activation: str = "silu"       # silu | gelu | relu2 | geglu
    norm_type: str = "rmsnorm"     # rmsnorm | layernorm
    qk_norm: bool = False
    tie_embeddings: bool = False

    # --- attention -----------------------------------------------------------
    rope_theta: float = 10000.0
    sliding_window: int = 0        # 0 = full attention
    global_attn_layers: Tuple[int, ...] = ()  # layers exempt from the window
    attn_logit_softcap: float = 0.0
    rope_scaling: Optional[YarnScaling] = None

    # --- multi-head latent attention (DeepSeek-V2 MLA; kv_lora_rank > 0) ----
    # K/V are compressed to one kv_lora_rank latent per token plus one
    # qk_rope_head_dim RoPE key shared by every head; the cache holds those.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # --- encoder / seq2seq ---------------------------------------------------
    is_encoder_only: bool = False
    is_encoder_decoder: bool = False
    num_encoder_layers: int = 0

    # --- MoE -----------------------------------------------------------------
    num_experts: int = 0
    num_experts_per_tok: int = 0
    expert_pad_multiple: int = 1   # pad expert count so it shards on `model`
    num_shared_experts: int = 0
    shared_expert_d_ff: int = 0    # total width of the shared-expert MLP
    shared_expert_gated: bool = True   # sigmoid gate on the shared experts
    norm_topk_prob: bool = True        # renormalise the top-k gates to 1
    routed_scaling_factor: float = 1.0  # gates' scale when not renormalised
    first_k_dense_replace: int = 0     # leading layers with a dense MLP
    dense_d_ff: int = 0                # their width (0 -> d_ff)
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    router_z_coef: float = 1e-3

    # --- SSM / hybrid --------------------------------------------------------
    ssm_state_dim: int = 16
    ssm_expand: int = 2            # d_inner = ssm_expand * d_model (mamba)
    ssm_conv_width: int = 4
    rwkv_head_dim: int = 64
    num_meta_tokens: int = 0       # hymba learnable prefix tokens

    # --- modality frontends (stubbed per the brief) --------------------------
    modality: str = "text"         # text | vision_text | audio
    num_patch_tokens: int = 0      # VLM: precomputed patch embeddings
    frontend_dim: int = 0          # dim of the stub embeddings (0 -> d_model)

    # --- blockwise parallel decoding (the paper's technique) -----------------
    bpd_k: int = 8                 # number of prediction heads p_1..p_k
    bpd_hidden: int = 0            # head FFN hidden size (0 -> d_ff heuristic)
    bpd_enabled: bool = True       # hubert: no autoregressive decode
    bpd_identity_p1: bool = True   # paper footnote 1: identity head for p_1

    # --- numerics ------------------------------------------------------------
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    max_seq_len: int = 8192
    remat: bool = False            # per-block activation checkpointing (train)

    # ------------------------------------------------------------------------
    @property
    def padded_vocab_size(self) -> int:
        """Vocab rounded up to a multiple of 256 so the embedding table and
        lm_head shard evenly on the model axis (MaxText-style padding).  The
        pad logits are masked to -inf in ``project_vocab``; token ids are
        always < vocab_size so embedding lookups never see the pad rows."""
        return ((self.vocab_size + 255) // 256) * 256

    @property
    def padded_num_experts(self) -> int:
        """Expert count rounded up to ``expert_pad_multiple`` so the expert
        dim of the MoE weights/buffers divides the model mesh axis (qwen2's
        60 experts pad to 64 = 4 dead lanes; the router never selects ids
        >= num_experts, so pad experts receive no tokens)."""
        if not self.num_experts:
            return 0
        m = max(self.expert_pad_multiple, 1)
        return ((self.num_experts + m - 1) // m) * m

    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // max(self.num_heads, 1)

    @property
    def resolved_bpd_hidden(self) -> int:
        return self.bpd_hidden or min(self.d_ff, 4 * self.d_model)

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    def layer_mlp_type(self, layer_idx: int) -> str:
        """The MLP of layer ``layer_idx``: a MoE stack's first
        ``first_k_dense_replace`` layers are dense."""
        if self.mlp_type == "moe" and layer_idx < self.first_k_dense_replace:
            return "dense"
        return self.mlp_type

    @property
    def compute_dtype(self):
        return DTYPES[self.dtype]

    @property
    def params_dtype(self):
        return DTYPES[self.param_dtype]

    @property
    def num_kv_groups(self) -> int:
        return max(self.num_heads, 1) // max(self.num_kv_heads, 1)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def validate(self) -> None:
        if self.block_type == "attn" or self.block_type == "hymba":
            assert self.num_heads % max(self.num_kv_heads, 1) == 0, (
                f"{self.name}: num_heads={self.num_heads} not divisible by "
                f"num_kv_heads={self.num_kv_heads}"
            )
        if self.mlp_type == "moe":
            assert self.num_experts > 0 and self.num_experts_per_tok > 0
        if self.is_mla:
            assert self.block_type == "attn" and self.qk_rope_head_dim % 2 == 0
            assert self.qk_nope_head_dim > 0 and self.v_head_dim > 0
        if self.block_type == "rwkv6":
            assert self.d_model % self.rwkv_head_dim == 0
        if self.is_encoder_decoder:
            assert self.num_encoder_layers > 0


@dataclasses.dataclass(frozen=True)
class DecodeConfig:
    """Paper §3-§5 decode-time parameters.

    ``policy`` names a registered ``core.policy.DecodePolicy`` (drafter ×
    acceptor × block schedule); empty string falls back to the legacy
    ``criterion`` alias, so existing configs decode unchanged.  The policy
    builders read their knobs (``top_k``, ``epsilon``, ``min_block``) off
    this config.  Policies whose drafter runs a second model
    (``policy="draft_model"``) additionally need an auxiliary
    ``core.bundle.ModelBundle`` passed to the session / decode entry
    point (``bundles={"draft": ...}``) — model identity lives in bundles,
    never in this config.
    """

    max_new_tokens: int = 64
    block_k: int = 0               # 0 -> model's bpd_k
    criterion: str = "exact"       # exact | topk | distance  (§3, §5.1, §5.2)
    policy: str = ""               # registered DecodePolicy name ("" -> criterion)
    top_k: int = 1                 # §5.1 top-k selection threshold
    epsilon: float = 0.0           # §5.2 distance-based tolerance
    min_block: int = 1             # §5.3 minimum accepted block size
    eos_id: int = -1               # -1: decode for max_new_tokens (image-style)
    temperature: float = 0.0       # 0 = greedy (paper setting)
    cache_backend: str = "dense"   # dense | paged (models.cache.get_backend)
    page_size: int = 16            # tokens per KV page (paged backend only)
    fused_verify: bool = False     # one-pass Pallas accept kernel (token-
    #                                identical opt-in; kernels/fused_verify.py)
    # 2-D raster geometry for the locality-aware image policy
    # (core.policy."locality"): the token stream is an image serialized in
    # the progressive-lattice order of data.synthetic.locality_order.
    image_height: int = 0          # grid rows (0 = not an image workload)
    image_width: int = 0           # grid cols
    locality_stride: int = 4       # coarse-lattice stride (power of two)

    def replace(self, **kw) -> "DecodeConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    seed: int = 0
    global_batch: int = 32
    seq_len: int = 256
    steps: int = 200
    # optimizer
    optimizer: str = "adamw"       # adamw | adafactor
    lr: float = 3e-4
    warmup_steps: int = 100
    schedule: str = "inv_sqrt"     # inv_sqrt | cosine | constant
    weight_decay: float = 0.01
    b1: float = 0.9
    b2: float = 0.98
    eps: float = 1e-9
    grad_clip: float = 1.0
    # BPD head training (paper §6)
    head_loss: str = "random"      # random (paper) | mean
    freeze_base: bool = False      # §6.1 fine-tuning ablation
    detach_head_residual: bool = False  # stabilized fine-tuning (see heads.py)
    label_smoothing: float = 0.0
    z_loss: float = 1e-4
    # Parallel scheduled sampling (arXiv:1906.04331): one extra no-grad
    # forward predicts every position; the conditioning prefix is mixed
    # gold -> model per position with an annealed probability so heads /
    # draft students train on decode-time distributions (targets stay gold
    # unless ss_self_targets).
    scheduled_sampling: bool = False
    ss_ratio: float = 0.5          # peak probability of a model-token swap
    ss_anneal_steps: int = 0       # linear 0 -> ss_ratio ramp (0 = constant)
    # Self-distilled targets: supervise heads with the frozen base's own
    # (deterministic) chain predictions instead of the (stochastic) gold
    # stream — exact-acceptance verification accepts a slot iff the head
    # matches p_1's chain, so this trains the actual acceptance condition
    # ("consistent mode breaking", the §6.2 distillation effect applied to
    # heads).  Only meaningful with scheduled_sampling and a frozen base.
    ss_self_targets: bool = False

    def __post_init__(self):
        valid_head_loss = ("random", "mean")
        if self.head_loss not in valid_head_loss:
            raise ValueError(
                f"TrainConfig.head_loss must be one of {valid_head_loss}, "
                f"got {self.head_loss!r}")
        if not 0.0 <= self.ss_ratio <= 1.0:
            raise ValueError(
                f"TrainConfig.ss_ratio must be in [0, 1], got {self.ss_ratio}")
        if self.ss_anneal_steps < 0:
            raise ValueError(
                f"TrainConfig.ss_anneal_steps must be >= 0, "
                f"got {self.ss_anneal_steps}")

    def replace(self, **kw) -> "TrainConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Production mesh description (v5e pod target)."""

    multi_pod: bool = False
    data: int = 16
    model: int = 16
    pods: int = 2

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.pods, self.data, self.model) if self.multi_pod else (self.data, self.model)

    @property
    def axes(self) -> Tuple[str, ...]:
        return ("pod", "data", "model") if self.multi_pod else ("data", "model")

    @property
    def num_devices(self) -> int:
        n = self.data * self.model
        return n * self.pods if self.multi_pod else n


# Input-shape grid assigned to this paper (see DESIGN.md §6).
INPUT_SHAPES = {
    "train_4k": dict(kind="train", seq_len=4096, global_batch=256),
    "prefill_32k": dict(kind="prefill", seq_len=32768, global_batch=32),
    "decode_32k": dict(kind="decode", seq_len=32768, global_batch=128),
    "long_500k": dict(kind="decode", seq_len=524288, global_batch=1),
}
