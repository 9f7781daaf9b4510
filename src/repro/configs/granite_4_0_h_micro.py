"""granite-4.0-h-micro [hybrid] — 40 layers d_model=2048, a period of ten:
five Mamba2 layers, one NoPE GQA attention layer (32H kv=8, hd=64), four
Mamba2 layers; every layer is followed by a SwiGLU MLP (d_ff=8192).  Mamba2:
expand 2 (64 heads of 64), d_state 128, one group, conv width 4 with a bias.
Scalars: h = 12 * embed, h += 0.22 * block(h), attention scale 1/64,
logits / 8; RMSNorm eps 1e-5; vocab 100352, tied.  Attention runs the
online-softmax (chunked) path, so a 4k-token backward holds no (S, S)
score matrix.
[hf:ibm-granite/granite-4.0-h-micro config.json]"""
from repro.models.common import ArchConfig

CONFIG = ArchConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    d_ff=8192,
    vocab=100352,
    block_pattern=("mamba2_mlp",) * 5 + ("attn",) + ("mamba2_mlp",) * 4,
    ssm_state=128,
    ssm_head_dim=64,
    ssm_expand=2,
    conv_width=4,
    conv_bias=True,
    position_embedding="nope",
    attn_scale=0.015625,
    embed_multiplier=12.0,
    residual_multiplier=0.22,
    logit_divisor=8.0,
    norm_eps=1e-5,
    tie_embeddings=True,
    round_mode="client_parallel",
    attn_impl="chunked",  # online softmax over KV blocks: O(S) memory at 4k+
    long_context_ok=True,
    source="hf:ibm-granite/granite-4.0-h-micro",
)
