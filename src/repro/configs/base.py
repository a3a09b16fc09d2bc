"""Config system: model / training / run configs and the 4 shape presets."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | lfm2 | rwkv6 | zamba2 | encdec | cnn
    n_layers: int
    d_model: int
    vocab: int
    d_ff: int = 0
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int | None = None  # None -> d_model // n_heads
    qk_norm: bool = False
    qkv_bias: bool = False
    act: str = "silu"
    norm_eps: float = 1e-6
    tie_embeddings: bool = True
    scale_embed: bool = False
    rope_theta: float = 1e4
    # gemma3-style local:global attention
    local_window: int | None = None
    global_every: int = 0  # every Nth layer is global; 0 = all global
    rope_theta_global: float | None = None
    # MoE
    n_experts: int = 0
    moe_top_k: int = 0
    capacity_factor: float = 1.25
    d_ff_expert: int = 0  # routed experts' width, where it differs from d_ff
    # The routed experts this chip holds, (first, count); None holds all.
    # The router still scores every one of n_experts.
    experts_held: tuple[int, int] | None = None
    # Hybrid stacks (lfm2): each layer's mixer ("conv" | "full_attention"),
    # and how many leading layers have a dense FFN in place of experts.
    layer_types: tuple[str, ...] = ()
    n_dense_layers: int = 0
    # SSM / hybrid
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    conv_width: int = 4  # causal depthwise conv taps (SSM; lfm2's short conv)
    shared_attn_every: int = 0  # zamba2: shared attn block cadence
    # encoder-decoder
    n_enc_layers: int = 0
    enc_seq: int = 4096  # stub audio-frontend frame count
    max_seq: int = 524288

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    @property
    def held(self) -> tuple[int, int]:
        """(first, count) of the routed experts held here."""
        return self.experts_held or (0, self.n_experts)

    @property
    def attention_free(self) -> bool:
        return self.family == "rwkv6"


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One assigned input-shape cell."""

    name: str
    kind: str  # train | prefill | decode
    seq_len: int
    global_batch: int


# The assigned shape set (applies to every architecture).
SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524288, 1),
}


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10000
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    microbatch: int = 0  # 0 = no gradient accumulation
    remat: str = "block"  # none | dots | block
    zero1: bool = True  # shard optimizer state over the data axis
    seed: int = 0
    loss_chunks: int = 8  # chunked cross-entropy over tokens
    grad_compression: str = "none"  # none | int8_ef
    # Run the family's planned Pallas kernels (forward AND planned
    # backward) in the train step instead of the XLA reference path:
    # cnn = fused conv + dgrad/wgrad + dX/dW matmul, transformer = every
    # block GEMM + flash attention + dX/dW (the family's make_loss_fn
    # hook owns the dispatch).  Slow in interpret mode off-TPU; the hot
    # path on TPU.
    planned_kernels: bool = False


@dataclasses.dataclass(frozen=True)
class RunConfig:
    model: ModelConfig
    train: TrainConfig
    shape: ShapeConfig
