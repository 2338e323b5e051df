"""Model configuration for the unified LM zoo.

One `ModelConfig` drives every assigned architecture: dense GQA, MLA, MoE,
sliding-window, RWKV6, Mamba-hybrid, plus modality-frontend stubs and the
optional SAM memory-layer augmentation (the paper's technique as a
first-class LM feature)."""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-V2 Multi-head Latent Attention."""
    kv_lora: int = 512
    q_lora: int = 1536
    rope_head_dim: int = 64
    nope_head_dim: int = 128
    v_head_dim: int = 128
    absorb: bool = False     # absorbed decode (q projected into latent space)


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_expert: int
    shared_experts: int = 0
    num_dense_layers: int = 0       # leading layers with a dense FFN
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.001


@dataclasses.dataclass(frozen=True)
class RWKVConfig:
    head_size: int = 64
    decay_lora: int = 64
    mix_lora: int = 32
    gate_lora: int = 64


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    state_size: int = 16
    expand: int = 2
    dt_rank: int = 64
    conv_width: int = 4


@dataclasses.dataclass(frozen=True)
class MemoryLayerConfig:
    """SAM external memory attached to the LM (paper technique, LM-scale).

    Each augmented layer reads top-K slots from a per-sequence external
    memory via content addressing and writes the current segment summary
    back to {previously-read ∪ LRA} slots — the SAM scheme of §3.1/§3.2."""
    num_slots: int = 65536
    word_size: int = 128
    num_heads: int = 4
    k: int = 8
    every_n_layers: int = 4
    delta: float = 0.005
    segment: int = 512
    # Kernel backend for the memory ops ('ref' | 'pallas' |
    # 'pallas-interpret' | registered custom; None -> the platform default,
    # 'pallas' on a TPU and 'ref' elsewhere — kernels/registry.py).
    backend: "str | None" = None
    # Storage dtype of the memory rows ('float32' | 'bfloat16' | 'int8'):
    # bfloat16 halves the (B, N+1, W) buffer; 'int8' quarters it, storing
    # per-row symmetric int8 words plus an f32 scale leaf (MemoryState.
    # mem_scale) that the fused kernels dequantize in-VMEM. Reads upcast to
    # float32 before the similarity/softmax math on every storage dtype;
    # see docs/memory-model.md ("storage dtype ladder") for the error
    # model and gradient semantics.
    mem_dtype: str = "float32"
    # How the segment loop backpropagates (core/unroll.py): 'naive' scans
    # and checkpoints the (B, N+1, W) memory per segment; 'sparse' stores
    # only the per-segment rollback deltas; 'chunked' adds boundary
    # checkpoints every `unroll_chunk` segments (None -> auto √-rule).
    unroll_mode: str = "sparse"
    unroll_chunk: "int | None" = None


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 128
    block: str = "dense"            # dense | moe | rwkv | hybrid
    window: Optional[int] = None    # sliding-window attention
    prefix_lm: int = 0              # bidirectional prefix length (VLM)
    rope_theta: float = 10000.0
    act: str = "silu"               # silu (gated) | gelu
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    mla: Optional[MLAConfig] = None
    moe: Optional[MoEConfig] = None
    rwkv: Optional[RWKVConfig] = None
    ssm: Optional[SSMConfig] = None
    frontend: Optional[str] = None  # 'audio' | 'vision' (stubbed embeddings)
    frontend_len: int = 0           # prefix embedding length provided by stub
    memory: Optional[MemoryLayerConfig] = None
    # numerics / scan
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    remat: bool = True
    q_block: int = 512              # chunked-attention block sizes
    kv_block: int = 512
    loss_chunk: int = 512           # sequence chunking for big-vocab loss
    causal_skip: bool = True        # skip fully-masked KV blocks (perf)
    # SAM-style sparse top-K block decode over the KV cache (§Perf C2):
    # None = dense decode; an int = number of blocks attended per step.
    sparse_decode_blocks: Optional[int] = None
    sparse_decode_block: int = 64
    # Pad each GQA head group to this many q-heads (zero-init, masked, never
    # trained) so the head dim divides the model mesh axis — replicated
    # attention becomes sharded attention (§Perf A2). None = no padding.
    pad_head_groups: Optional[int] = None

    @property
    def padded_heads(self) -> int:
        if self.pad_head_groups is None:
            return self.num_heads
        return self.num_kv_heads * self.pad_head_groups

    @property
    def q_heads_per_kv(self) -> int:
        return self.num_heads // self.num_kv_heads

    @property
    def sub_quadratic(self) -> bool:
        """True if decode state does not grow linearly without bound
        (SSM/linear-attention state or a bounded SWA window)."""
        return self.block in ("rwkv",) or self.window is not None

    def scaled(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)
