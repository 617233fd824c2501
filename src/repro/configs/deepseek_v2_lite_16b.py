"""deepseek-v2-lite-16b [moe] — MLA kv_lora=512, shared+routed experts top-6
[arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite config.json].

27L d_model=2048 16H vocab=102400, untied lm_head, RMSNorm eps 1e-6.
Attention is MLA without q-LoRA: latent 512 (RMS-normed before the cache),
qk_nope 128 + qk_rope 64, v 128, YaRN rope (factor 40 over 4096, β 32/1,
mscale = mscale_all_dim = 0.707).  Layer 0 (``first_k_dense_replace``) has
a dense SiLU MLP of width 10944; layers 1-26 are MoE: 64 routed experts of
width 1408, top-6 of a softmax, weights not renormalised
(``norm_topk_prob`` false, ``routed_scaling_factor`` 1), plus 2 shared.
The MLA latent cache (1152 bytes a position a layer in bf16) is the decode
memory win."""
from repro.models import ModelConfig

_YARN = dict(rope_theta=10000.0, rope_factor=40.0, rope_original_len=4096,
             rope_beta_fast=32.0, rope_beta_slow=1.0, rope_mscale=0.707,
             rope_mscale_all_dim=0.707)


def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite-16b", family="moe",
        num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
        d_ff=10944, vocab_size=102400, head_dim=128,
        block_pattern=("mla",), first_k_dense=1, tie_embeddings=False,
        num_experts=64, experts_per_tok=6, num_shared_experts=2,
        moe_d_ff=1408, norm_topk_prob=False,
        kv_lora_rank=512, rope_head_dim=64, norm_eps=1e-6, **_YARN,
    )


def reduced() -> ModelConfig:
    """One dense layer and two MoE layers; 8 experts routed over, 4 held
    (experts 0-3, one chip's share of an EP-2 layout); untied; YaRN."""
    return ModelConfig(
        name="deepseek-reduced", family="moe",
        num_layers=3, d_model=64, num_heads=4, num_kv_heads=4,
        d_ff=96, vocab_size=256, head_dim=16, block_pattern=("mla",),
        first_k_dense=1, tie_embeddings=False,
        num_experts=8, experts_held=4, experts_per_tok=2,
        num_shared_experts=1, moe_d_ff=32, norm_topk_prob=False,
        kv_lora_rank=16, rope_head_dim=8, norm_eps=1e-6,
        attn_chunk=8, dtype="float32", **_YARN,
    )
