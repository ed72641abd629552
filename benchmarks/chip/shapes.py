"""Operations and bytes that a dense decoder's programs need, from shapes.

Every count here is the work that real tokens require: no padding, keys and
values read only up to each request's own length, no recomputation in the
backward pass.  ``least_seconds`` turns a count into the least time a chip
needs for it, from the peaks table; the ``mfu.*`` metrics divide the sum of
those least times by the wall time of the window.

``Sizes`` is read from a configuration file (``configs/<name>.json``); the
keys follow the Hugging Face ``config.json`` names.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib

BF16 = 2
HERE = pathlib.Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Sizes:
    d: int            # hidden_size
    layers: int       # num_hidden_layers
    heads: int        # num_attention_heads
    kv_heads: int     # num_key_value_heads
    head_dim: int
    ff: int           # intermediate_size
    vocab: int
    tied: bool
    rope_theta: float
    norm_eps: float

    @classmethod
    def from_config(cls, cfg: dict) -> "Sizes":
        return cls(d=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
                   heads=cfg["num_attention_heads"],
                   kv_heads=cfg["num_key_value_heads"],
                   head_dim=cfg["head_dim"], ff=cfg["intermediate_size"],
                   vocab=cfg["vocab_size"], tied=cfg["tie_word_embeddings"],
                   rope_theta=float(cfg["rope_theta"]),
                   norm_eps=float(cfg["rms_norm_eps"]))

    # --- parameters -------------------------------------------------------

    @property
    def layer_matmul_params(self) -> int:
        """q, k, v and output projections plus the gated MLP, one layer."""
        attn = self.d * self.head_dim * (2 * self.heads + 2 * self.kv_heads)
        return attn + 3 * self.d * self.ff

    @property
    def layer_params(self) -> int:
        return self.layer_matmul_params + 2 * self.d      # two norm gains

    @property
    def embed_params(self) -> int:
        return self.vocab * self.d * (1 if self.tied else 2)

    @property
    def params(self) -> int:
        return self.layers * self.layer_params + self.embed_params + self.d

    @property
    def weight_bytes(self) -> int:
        return self.params * BF16

    @property
    def kv_bytes_per_token(self) -> int:
        return self.layers * 2 * self.kv_heads * self.head_dim * BF16

    # --- forward operations ----------------------------------------------

    def attn_flops(self, n_queries: int, first_pos: int) -> float:
        """Scores and weighted values of ``n_queries`` causal queries at
        positions first_pos .. first_pos+n_queries-1, all layers."""
        keys = n_queries * first_pos + n_queries * (n_queries + 1) / 2
        return 4.0 * self.layers * self.heads * self.head_dim * keys

    def forward_flops(self, n_tokens: int, first_pos: int,
                      n_logits: int) -> float:
        """One sequence: ``n_tokens`` new tokens after ``first_pos`` cached
        ones, with the output head applied to ``n_logits`` positions."""
        return (2.0 * self.layers * self.layer_matmul_params * n_tokens
                + 2.0 * self.d * self.vocab * n_logits
                + self.attn_flops(n_tokens, first_pos))


def load_sizes(config_file: str | pathlib.Path) -> Sizes:
    return Sizes.from_config(json.loads(pathlib.Path(config_file).read_text()))


# --- work per program -------------------------------------------------------
# Each returns (flops, bytes) of the work that the program's real tokens need.

def prefill_work(s: Sizes, prompt_lens: list[int]) -> tuple[float, float]:
    """One batched prefill: each prompt's own tokens, logits of its last
    position only, and its keys and values written to the cache."""
    flops = sum(s.forward_flops(n, 0, 1) for n in prompt_lens)
    return flops, s.weight_bytes + s.kv_bytes_per_token * sum(prompt_lens)


def decode_work(s: Sizes, context_lens: list[int]) -> tuple[float, float]:
    """One decode step for the requests still generating; ``context_lens``
    holds each one's length before this step's token."""
    flops = sum(s.forward_flops(1, n, 1) for n in context_lens)
    kv = s.kv_bytes_per_token * sum(n + 1 for n in context_lens)
    return flops, s.weight_bytes + kv


# AdamW with float32 moments: read the bf16 weights in the forward and the
# backward pass, write the gradient, then read and write weights, gradient
# and both moments once.
TRAIN_BYTES_PER_PARAM = 2 * BF16 + 4 + (2 * BF16 + 4 + 2 * 8)


def train_work(s: Sizes, batch: int, seq: int) -> tuple[float, float]:
    """One training step: forward and backward (3x the forward's
    operations), logits at every position, no recomputation."""
    flops = 3.0 * batch * s.forward_flops(seq, 0, seq)
    return flops, TRAIN_BYTES_PER_PARAM * s.params


def least_seconds(work: tuple[float, float], peaks: dict) -> float:
    flops, nbytes = work
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def peaks_for(device_kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json; known: "
                       f"{[k for k in table if k != 'source']}")
    return table[device_kind]
