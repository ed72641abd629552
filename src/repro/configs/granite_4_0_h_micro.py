"""Granite-4.0-H Micro [hf:ibm-granite/granite-4.0-h-micro].

40 layers: Mamba-2 mixers, with causal GQA at layers 5, 15, 25 and 35 and
no position embedding (NoPE); every mixer is followed by a SiLU-gated MLP.
Mamba-2: d_inner 4096 in 64 heads of 64, d_state 128, one B/C group,
conv 4, chunk 256.  Attention and MLP widths are granite-3-2b's.  The
Granite multipliers: input embedding x12, scores x1/64, each block's
output x0.22 into the residual, logits /8; tied embedding.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-micro", family="hybrid",
    n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8, head_dim=64,
    d_ff=8192, vocab_size=100_352,
    position_embedding="nope", attention_multiplier=0.015625,
    # 128-key steps: a 32 x 2048-token prefill's scores then take 1 GiB,
    # not 4, and the prefill fits one 16 GiB chip beside the cache
    attn_kv_block=128,
    layer_types=tuple("attention" if i % 10 == 5 else "mamba"
                      for i in range(40)),
    ssm_state=128, ssm_conv=4, ssm_expand=2, mamba_version=2,
    ssm_head_dim=64, ssm_groups=1, ssm_chunk=256,
    norm_eps=1e-5, tie_embeddings=True,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    logits_scaling=8.0,
)
