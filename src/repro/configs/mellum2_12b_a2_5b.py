"""mellum2-12b-a2.5b: GQA 32/4 in a [window, window, window, full]
period - window layers 1024 rows with RoPE 5e5, full layers YaRN
(factor 16 over 8192) - and 64 routed experts of width 896 in every
layer, top-8, top-k renormalised, no shared expert [hf:JetBrains/
Mellum2-12B-A2.5B-Instruct config.json].  The MTP head is not built."""
from repro.configs.base import AttnKind, ModelConfig, Yarn, register

THETA = 500000.0
WINDOW = AttnKind(window=1024, rope_theta=THETA)
FULL = AttnKind(rope_theta=THETA, yarn=Yarn(
    factor=16.0, original=8192, beta_fast=32.0, beta_slow=1.0,
    attention_factor=1.2772588722239782))

MELLUM2_12B_A2_5B = register(ModelConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    n_layers=28,
    d_model=2304,
    n_heads=32,
    n_kv_heads=4,
    d_head=128,
    d_ff=896,                   # moe_intermediate_size; every layer sparse
    vocab_size=98304,
    rope_theta=THETA,
    attn_pattern=(WINDOW, WINDOW, WINDOW, FULL),
    n_experts=64,
    moe_top_k=8,
    attn_impl="hfa",
    norm_eps=1e-6,
    tie_embeddings=False,
    param_dtype="bfloat16",
))
