"""Operations and bytes that a layer-pattern hybrid's serving programs need
(granite-4.0-h: Mamba-2 layers and attention layers, an MLP after every
mixer), from shapes.

As in ``shapes.py``, every count is the work that real tokens require: no
padding, keys and values read only up to each request's own length, the
state recurrence counted as the recurrence (the chunked form's extra
products are the program's choice).  ``shapes.least_seconds`` turns a
count into the least time a chip needs for it.

``HybridSizes`` is read from a configuration file whose keys follow the
Hugging Face ``config.json`` names (``layer_types``, ``mamba_*``).
"""

from __future__ import annotations

import dataclasses

BF16, F32 = 2, 4


@dataclasses.dataclass(frozen=True)
class HybridSizes:
    d: int                # hidden_size
    layers: int           # num_hidden_layers
    heads: int            # num_attention_heads
    kv_heads: int         # num_key_value_heads
    head_dim: int
    ff: int               # intermediate_size (the MLP after every mixer)
    vocab: int
    tied: bool
    norm_eps: float
    layer_types: tuple[str, ...]
    mamba_heads: int
    mamba_head_dim: int
    state: int            # mamba_d_state
    groups: int           # mamba_n_groups
    conv: int             # mamba_d_conv
    chunk: int            # mamba_chunk_size
    embedding_multiplier: float
    attention_multiplier: float
    residual_multiplier: float
    logits_scaling: float

    @classmethod
    def from_config(cls, cfg: dict) -> "HybridSizes":
        return cls(
            d=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
            heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
            tied=cfg["tie_word_embeddings"],
            norm_eps=float(cfg["rms_norm_eps"]),
            layer_types=tuple(cfg["layer_types"]),
            mamba_heads=cfg["mamba_n_heads"],
            mamba_head_dim=cfg["mamba_d_head"], state=cfg["mamba_d_state"],
            groups=cfg["mamba_n_groups"], conv=cfg["mamba_d_conv"],
            chunk=cfg["mamba_chunk_size"],
            embedding_multiplier=float(cfg["embedding_multiplier"]),
            attention_multiplier=float(cfg["attention_multiplier"]),
            residual_multiplier=float(cfg["residual_multiplier"]),
            logits_scaling=float(cfg["logits_scaling"]))

    # --- layer counts and widths --------------------------------------------

    @property
    def n_attn(self) -> int:
        return self.layer_types.count("attention")

    @property
    def n_mamba(self) -> int:
        return self.layer_types.count("mamba")

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels of xBC: x, and B and C of every group."""
        return self.d_inner + 2 * self.groups * self.state

    @property
    def in_proj_width(self) -> int:
        """z, xBC and dt."""
        return self.d_inner + self.conv_dim + self.mamba_heads

    # --- parameters ----------------------------------------------------------

    @property
    def mixer_matmul_params(self) -> int:
        return self.d * self.in_proj_width + self.d_inner * self.d

    @property
    def mixer_bytes(self) -> int:
        """One Mamba-2 mixer's weights: in and out projections, conv
        weights and bias and the gated norm's gain in bfloat16; dt_bias,
        A_log and D in float32."""
        bf16 = (self.mixer_matmul_params + self.conv * self.conv_dim
                + self.conv_dim + self.d_inner)
        return bf16 * BF16 + 3 * self.mamba_heads * F32

    @property
    def attn_matmul_params(self) -> int:
        return self.d * self.head_dim * (2 * self.heads + 2 * self.kv_heads)

    @property
    def mlp_params(self) -> int:
        return 3 * self.d * self.ff

    @property
    def weight_bytes(self) -> int:
        """Every weight: the mixers, the attention layers, one MLP and two
        norm gains per layer, the (tied) embedding and the final norm."""
        attn = (self.attn_matmul_params + self.mlp_params + 2 * self.d) * BF16
        mamba = self.mixer_bytes + (self.mlp_params + 2 * self.d) * BF16
        embed = self.vocab * self.d * (1 if self.tied else 2)
        return (self.n_attn * attn + self.n_mamba * mamba
                + (embed + self.d) * BF16)

    # --- cache ---------------------------------------------------------------

    @property
    def layer_state_bytes(self) -> int:
        """One sequence's state in one mamba layer: the float32 SSM state
        and the bfloat16 window of the last conv - 1 xBC rows."""
        return (self.mamba_heads * self.mamba_head_dim * self.state * F32
                + (self.conv - 1) * self.conv_dim * BF16)

    @property
    def state_bytes_per_seq(self) -> int:
        return self.n_mamba * self.layer_state_bytes

    @property
    def kv_bytes_per_token(self) -> int:
        return self.n_attn * 2 * self.kv_heads * self.head_dim * BF16

    # --- forward operations --------------------------------------------------

    @property
    def mamba_token_flops(self) -> float:
        """One token through one mixer: the projections, the conv, and the
        recurrence (decay, outer product and add into the state, then the
        state times C: 5 operations per state element)."""
        return (2.0 * self.mixer_matmul_params
                + 2.0 * self.conv * self.conv_dim
                + 5.0 * self.d_inner * self.state)

    def forward_flops(self, n_tokens: int, first_pos: int,
                      n_logits: int) -> float:
        """One sequence: ``n_tokens`` new tokens after ``first_pos`` cached
        ones, with the output head applied to ``n_logits`` positions."""
        keys = n_tokens * first_pos + n_tokens * (n_tokens + 1) / 2
        return (n_tokens * (self.n_mamba * self.mamba_token_flops
                            + self.n_attn * 2.0 * self.attn_matmul_params
                            + self.layers * 2.0 * self.mlp_params)
                + 4.0 * self.n_attn * self.heads * self.head_dim * keys
                + 2.0 * self.d * self.vocab * n_logits)


# --- work per program --------------------------------------------------------
# Each returns (flops, bytes) of the work that the program's real tokens need.

def prefill_work(s: HybridSizes, prompt_lens: list[int]
                 ) -> tuple[float, float]:
    """One batched prefill: each prompt's tokens, logits of its last
    position, its keys and values and its end state written to the
    cache."""
    flops = sum(s.forward_flops(n, 0, 1) for n in prompt_lens)
    return flops, (s.weight_bytes + s.kv_bytes_per_token * sum(prompt_lens)
                   + s.state_bytes_per_seq * len(prompt_lens))


def decode_work(s: HybridSizes, context_lens: list[int]
                ) -> tuple[float, float]:
    """One decode step for the requests still generating; ``context_lens``
    holds each one's length before this step's token: every weight, each
    request's state read and written, its keys and values read."""
    flops = sum(s.forward_flops(1, n, 1) for n in context_lens)
    kv = s.kv_bytes_per_token * sum(n + 1 for n in context_lens)
    state = 2 * s.state_bytes_per_seq * len(context_lens)
    return flops, s.weight_bytes + kv + state


def ssm_decode_bytes(s: HybridSizes, live: int) -> int:
    """What the mamba mixers of one decode step must move: their weights,
    and the conv window and SSM state of ``live`` requests read and
    written."""
    return s.n_mamba * (s.mixer_bytes + 2 * live * s.layer_state_bytes)
