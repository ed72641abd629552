"""Model/shape configuration system.

``ModelConfig`` is the single source of truth consumed by the model builder,
the sharding rules, the launcher and the dry-run.  One module per assigned
architecture lives next to this file; ``registry.get(name)`` loads it.
"""

from __future__ import annotations

import dataclasses
from typing import Literal

Family = Literal["dense", "moe", "ssm", "hybrid", "vlm", "audio"]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: Family
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                      # 0 -> d_model // n_heads

    # --- attention variants ---
    rope_theta: float = 10_000.0
    position_embedding: str = "rope"       # rope | nope (none at all)
    attention_multiplier: float = 0.0      # score scale; 0 -> 1/sqrt(Dh)
    attn_kv_block: int = 512               # keys per step of the block scan
    #   (prefill / training): its scores are (B, T, heads, block) float32
    sliding_window: int = 0                # >0: local-attention window size
    local_global_every: int = 0            # N: every Nth layer is global
    attn_logit_softcap: float = 0.0        # gemma2-style tanh capping
    final_logit_softcap: float = 0.0
    qk_norm: bool = False

    # --- MoE ---
    n_experts: int = 0
    n_experts_active: int = 0              # top-k
    moe_d_ff: int = 0                      # routed expert hidden dim
    shared_expert_d_ff: int = 0            # shared expert(s) hidden dim
    moe_every: int = 1                     # llama4: MoE every Nth layer

    # --- SSM (mamba) ---
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    mamba_version: int = 1                 # 1: falcon-mamba, 2: zamba2
    ssm_head_dim: int = 64                 # mamba2 heads
    ssm_groups: int = 1                    # mamba2 B/C groups (n_groups)
    ssm_chunk: int = 256                   # mamba2 SSD chunk length

    # --- hybrid (zamba2) ---
    attn_every: int = 0                    # insert shared attn block every N
    n_shared_attn_blocks: int = 0          # distinct shared blocks, cycled

    # --- hybrid by layer pattern (granite-4.0-h) ---
    layer_types: tuple[str, ...] = ()      # per layer "mamba" | "attention"
    #   mixer, each followed by the MLP; empty: zamba2's shared blocks

    # --- multimodal stubs ---
    cross_attn_every: int = 0              # vlm: cross-attn block every N
    n_media_tokens: int = 0                # vision/audio stub token count
    media_embed_dim: int = 0               # stub frontend output dim

    # --- misc ---
    norm_eps: float = 1e-6
    act: str = "silu"                      # silu | gelu
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    # granite multipliers; 0 / 1 leave each family's default in place
    embedding_multiplier: float = 0.0      # input embedding scale
    residual_multiplier: float = 1.0       # scale of each block's output
    logits_scaling: float = 1.0            # logits are divided by this

    # --- framework features ---
    remat_policy: str = "dots"             # none | dots | full
    overlap: str = "none"                  # none | shared_bus (paper technique)
    constrain_activations: bool = False    # pin residual stream to pure-DP
    #   sharding at layer boundaries (weights gather; activations stay put)
    unroll_layers: bool = False            # dry-run cost probes: XLA counts
    #   scan bodies once, so probes compile fully unrolled (dryrun.py)

    def __post_init__(self):
        if self.head_dim == 0 and self.n_heads > 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if self.layer_types and len(self.layer_types) != self.n_layers:
            raise ValueError(f"{self.name}: {len(self.layer_types)} "
                             f"layer_types for {self.n_layers} layers")

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def supports_long_context(self) -> bool:
        """Sub-quadratic long-context: SSM / hybrid / mostly-local attention.

        The local:global allowance requires a mostly-local design (>= 4
        local layers per global, e.g. gemma3's 5:1 128k-context recipe);
        gemma2's 1:1 alternation is an 8k-context design and is excluded
        (DESIGN.md Sec 5)."""
        return self.family in ("ssm", "hybrid") or (
            self.sliding_window > 0 and self.local_global_every >= 5)

    def with_depth(self, n_layers: int) -> "ModelConfig":
        """The same widths with only ``n_layers`` layers: a depth-only cut
        that keeps every per-layer shape (and so the per-layer cost) as
        published.  The cut must keep whole layer groups."""
        period = (self.n_layers // self.layer_types.count("attention")
                  if self.layer_types else 1)
        group = max(self.attn_every, self.cross_attn_every,
                    self.moe_every if self.n_experts else 1, period, 1)
        if not 0 < n_layers <= self.n_layers or n_layers % group:
            raise ValueError(
                f"{self.name}: cannot cut {self.n_layers} layers to "
                f"{n_layers} (a positive multiple of {group}, at most "
                f"{self.n_layers})")
        return dataclasses.replace(self, name=f"{self.name}-{n_layers}L",
                                   n_layers=n_layers,
                                   layer_types=self.layer_types[:n_layers])

    def reduced(self) -> "ModelConfig":
        """Tiny same-family config for CPU smoke tests."""
        def cut(v, lo=1):
            return max(lo, v)
        # two layers of each mixer, a mamba layer first as published
        pattern = (("mamba", "attention") * 2 if self.layer_types else ())
        return dataclasses.replace(
            self,
            name=self.name + "-smoke",
            n_layers=len(pattern) or min(
                self.n_layers, 4 if self.attn_every or
                self.cross_attn_every else 2),
            layer_types=pattern,
            d_model=64,
            n_heads=cut(min(self.n_heads, 4)),
            n_kv_heads=cut(min(self.n_kv_heads, 2)),
            head_dim=16 if self.n_heads else 0,
            d_ff=128 if self.d_ff else 0,
            vocab_size=256,
            sliding_window=min(self.sliding_window, 8) if self.sliding_window
            else 0,
            n_experts=min(self.n_experts, 8) if self.n_experts else 0,
            n_experts_active=min(self.n_experts_active, 2)
            if self.n_experts_active else 0,
            moe_d_ff=64 if self.moe_d_ff else 0,
            shared_expert_d_ff=64 if self.shared_expert_d_ff else 0,
            ssm_state=min(self.ssm_state, 8) if self.ssm_state else 0,
            ssm_head_dim=16 if self.family in ("ssm", "hybrid") else 64,
            ssm_chunk=min(self.ssm_chunk, 8),
            attn_every=min(self.attn_every, 2) if self.attn_every else 0,
            cross_attn_every=min(self.cross_attn_every, 2)
            if self.cross_attn_every else 0,
            n_media_tokens=min(self.n_media_tokens, 8)
            if self.n_media_tokens else 0,
            media_embed_dim=32 if self.media_embed_dim else 0,
        )


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: Literal["train", "prefill", "decode"]


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


def shape_applicable(cfg: ModelConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """Whether an (arch, shape) cell runs; reason recorded in EXPERIMENTS.md."""
    if shape.name == "long_500k" and not cfg.supports_long_context:
        return False, ("SKIP: pure full-attention architecture; 500k context "
                       "requires sub-quadratic attention (DESIGN.md Sec 5)")
    return True, "ok"
