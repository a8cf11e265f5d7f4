"""Model/run configuration.

One frozen dataclass describes an architecture; ``src/repro/configs/<id>.py``
files instantiate the 10 assigned architectures (plus reduced smoke variants)
and register them in ``repro.configs.registry``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | hybrid_rglru | xlstm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0              # 0 -> d_model // n_heads

    # MoE (dropless): the router scores all ``n_experts``; this device holds
    # ``experts_held`` = (first, count) of them, (0, 0) meaning all
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0              # expert width (0 -> d_ff); see expert_ff
    n_shared_experts: int = 0      # shared experts, one SwiGLU of n x moe_d_ff
    score_fn: str = "softmax"      # softmax | sigmoid (DeepSeek-V3 noaux_tc)
    norm_topk: bool = True         # renormalise the k chosen weights
    routed_scale: float = 1.0      # routed_scaling_factor
    experts_held: Sequence[int] = (0, 0)

    # multi-head latent attention (DeepSeek-V2/V3); 0 -> plain GQA
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # leading dense layers ("attn" blocks, unscanned) before the period
    first_dense_layers: int = 0

    # positions
    pos_type: str = "rope"         # rope | mrope | learned | none
    rope_theta: float = 10000.0
    mrope_sections: Sequence[int] = ()   # qwen2-vl t/h/w split of head_dim/2

    # block pattern (period definition); () -> ("attn",) * 1
    # kinds: attn | local_attn | rglru | mlstm | slstm | moe
    block_pattern: Sequence[str] = ()
    window: int = 0                # local attention window
    lru_width: int = 0             # RG-LRU recurrence width (0 -> d_model)
    conv_width: int = 4            # temporal conv in recurrent blocks
    mlstm_chunk: int = 256         # chunk size of the chunkwise mLSTM form

    # modality frontend stub: inputs are precomputed embeddings, not tokens
    embeds_input: bool = False

    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    mlp_variant: str = "swiglu"    # swiglu (3-matrix) | gelu (2-matrix)

    # numerics / compilation
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    remat: bool = True
    scan_layers: bool = True
    attention_impl: str = "auto"   # auto | ref | xla_flash | pallas

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if not self.block_pattern:
            kind = "moe" if self.family == "moe" else "attn"
            object.__setattr__(self, "block_pattern", (kind,))
        object.__setattr__(self, "block_pattern", tuple(self.block_pattern))
        object.__setattr__(self, "mrope_sections", tuple(self.mrope_sections))
        object.__setattr__(self, "experts_held", tuple(self.experts_held))
        if self.lru_width == 0:
            object.__setattr__(self, "lru_width", self.d_model)
        assert self.n_heads % self.n_kv_heads == 0, "GQA group must divide heads"

    # -- derived -------------------------------------------------------------
    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def qk_head_dim(self) -> int:
        """Per-head width of queries and keys (MLA: nope + rope slices)."""
        if self.is_mla:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.head_dim

    @property
    def value_head_dim(self) -> int:
        return self.v_head_dim if self.is_mla else self.head_dim

    @property
    def held(self) -> tuple:
        """(first, count) of the experts this device holds."""
        first, count = self.experts_held
        count = count or self.n_experts - first
        if not (0 <= first and first + count <= self.n_experts):
            raise ValueError(f"held experts {self.experts_held} outside the "
                             f"router's {self.n_experts}")
        return first, count

    @property
    def n_held(self) -> int:
        return self.held[1]

    @property
    def expert_ff(self) -> int:
        """Width of each routed and shared expert."""
        return self.moe_d_ff or self.d_ff

    @property
    def n_groups(self) -> int:
        """Number of full pattern periods (scanned)."""
        return (self.n_layers - self.first_dense_layers) \
            // len(self.block_pattern)

    @property
    def n_tail(self) -> int:
        """Layers after the last full period (executed unscanned)."""
        return (self.n_layers - self.first_dense_layers) \
            % len(self.block_pattern)

    def layer_kinds(self) -> list:
        """Block kind of every layer in order: leading dense layers, then
        the period repeated (the tail is the period's first blocks)."""
        period = self.block_pattern
        return ["attn"] * self.first_dense_layers + [
            period[i % len(period)]
            for i in range(self.n_layers - self.first_dense_layers)]

    @property
    def is_subquadratic(self) -> bool:
        """True if no block attends over unbounded full context ("moe"
        blocks carry full attention too; "local_attn" is windowed)."""
        return "attn" not in self.block_pattern and \
            "moe" not in self.block_pattern

    def param_count(self) -> int:
        """Exact parameter count (embedding + blocks + head)."""
        d, v = self.d_model, self.vocab_size
        total = v * d                      # embedding
        if not self.tie_embeddings:
            total += d * v                 # lm head
        total += d                         # final norm
        for kind in self.layer_kinds():
            total += self._block_params(kind)
        return total

    def active_param_count(self) -> int:
        """Params active per token (MoE: top_k of the held experts' share
        of the routed experts, i.e. top_k x held / n_experts expert passes)."""
        if self.n_experts == 0:
            return self.param_count()
        expert = 3 * self.d_model * self.expert_ff
        n_moe = self.layer_kinds().count("moe")
        return self.param_count() - n_moe * self.n_held * expert \
            + int(n_moe * expert * self.top_k * self.n_held / self.n_experts)

    def attn_params(self) -> int:
        d = self.d_model
        if self.is_mla:
            H, r = self.n_heads, self.kv_lora_rank
            return (d * H * self.qk_head_dim                    # q
                    + d * (r + self.qk_rope_head_dim) + r       # kv down, norm
                    + r * H * (self.qk_nope_head_dim + self.v_head_dim)
                    + H * self.v_head_dim * d)                  # out
        return d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d

    def _block_params(self, kind: str) -> int:
        d = self.d_model
        norm = d
        mlp_mats = 2 if self.mlp_variant == "gelu" else 3
        if kind in ("attn", "local_attn"):
            mlp = mlp_mats * d * self.d_ff if self.d_ff else 0
            return self.attn_params() + mlp + 2 * norm
        if kind == "moe":
            router = d * self.n_experts
            bias = self.n_experts if self.score_fn == "sigmoid" else 0
            experts = (self.n_held + self.n_shared_experts) \
                * 3 * d * self.expert_ff
            return self.attn_params() + router + bias + experts + 2 * norm
        if kind == "rglru":
            w = self.lru_width
            # in-proj (2 branches) + conv + gate vectors (w_a,b_a,w_i,b_i,lam)
            # + out-proj + mlp + norms
            rec = 2 * d * w + self.conv_width * w + 5 * w + w * d
            mlp = mlp_mats * d * self.d_ff if self.d_ff else 0
            return rec + mlp + 2 * norm
        if kind == "mlstm":
            inner = 2 * d
            up = 2 * d * inner          # up-proj (value + gate branches)
            # block-diagonal per-head q,k,v (the xLSTM implementation choice)
            qkv = 3 * inner * (inner // self.n_heads)
            gates = 2 * (inner * self.n_heads + self.n_heads)
            down = inner * d
            return up + qkv + gates + down + norm
        if kind == "slstm":
            gates = 4 * d * d + 4 * d * d + 4 * d   # w_in, w_rec, bias
            down = d * d
            return gates + down + norm
        raise ValueError(kind)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One input-shape cell of the assigned grid."""
    name: str
    kind: str            # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES = {
    "train_4k": ShapeConfig("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524288, 1),
}


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Training-run substrate settings (optimizer/schedule/fault-tolerance)."""
    learning_rate: float = 3e-4
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 1000
    seed: int = 0
    # microbatched gradient accumulation (scan over global-batch slices);
    # bounds activation peak memory at fixed global batch
    grad_accum: int = 1
    # preemption-aware checkpointing (the paper's policies)
    ckpt_dir: str = "runs/ckpt"     # relative to the working directory
    ckpt_policy: str = "dp"        # dp | young_daly | fixed | none
    ckpt_cost_hours: float = 1.0 / 60.0
    step_time_hours: float = 1.0 / 3600.0   # measured online; this is the seed
    vm_type: str = "tpu-v5e-pod"
    async_checkpoint: bool = True
    # MoE training (DeepSeek-V3): weight of the sequence-wise balance loss,
    # and the step of the aux-loss-free routing-bias update
    moe_seq_aux_alpha: float = 1e-4
    moe_bias_rate: float = 1e-3
