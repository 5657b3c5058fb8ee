"""deepseek-v2-lite-16b [moe]: DeepSeek-V2-Lite as published
(https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json,
arXiv:2405.04434).

* 27 layers at hidden size 2048; layer 0 has a dense SwiGLU FFN (10944),
  layers 1-26 a mixture of experts: 64 routed SwiGLU experts (1408), top 6
  by a softmax router with no renormalisation (``norm_topk_prob`` false,
  ``routed_scaling_factor`` 1), plus 2 shared experts (one SwiGLU of 2816).
* Latent attention (MLA), 16 heads, no query compression: ``kv_lora_rank``
  512 (RMSNorm'd), QK heads of 128 + 64 rope dimensions, PV heads of 128.
* YaRN rope scaling, factor 40 over an original context of 4096
  (``beta_fast`` 32, ``beta_slow`` 1, ``mscale`` = ``mscale_all_dim`` =
  0.707), rope theta 10000; RMSNorm epsilon 1e-6.
* A vocabulary of 102400 with an untied head, which runs digitally (bf16)
  beside the router, outside the photonic kernel.

``FULL`` holds every expert (``ep_size`` 1).  A chip's share under expert
parallelism over ``ep_size`` chips is ``num_experts`` = 64 / ``ep_size``
held experts with the router's width unchanged (``ModelConfig.ep_size``).
The paged ``repro.serving.Scheduler`` serves it: the latent pool holds
``c_kv`` and ``k_rope`` per token per layer, and the held experts run
dropless through one grouped photonic GEMM per site.
"""

import jax.numpy as jnp

from repro.models.common import ModelConfig
from repro.models.registry import make_arch

YARN = {
    "beta_fast": 32,
    "beta_slow": 1,
    "factor": 40,
    "mscale": 0.707,
    "mscale_all_dim": 0.707,
    "original_max_position_embeddings": 4096,
    "type": "yarn",
}

# The router and the untied head run digitally, outside the photonic kernel.
HEAD_DIGITAL = ("router", "lm_head")

FULL = ModelConfig(
    arch_id="deepseek-v2-lite-16b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=10944,           # dense first layer
    vocab_size=102400,
    rope_theta=10000.0,
    norm_eps=1e-6,
    mla=True,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_scaling=YARN,
    num_experts=64,
    num_experts_per_tok=6,
    num_shared_experts=2,
    moe_d_ff=1408,        # per routed expert
    norm_topk_prob=False,
    routed_scaling_factor=1.0,
    first_dense_layers=1,
    photonic_exclude=HEAD_DIGITAL,
    param_dtype=jnp.bfloat16,
    compute_dtype=jnp.bfloat16,
)

SMOKE = ModelConfig(
    arch_id="deepseek-v2-lite-smoke",
    family="moe",
    num_layers=3,
    d_model=64,
    num_heads=4,
    num_kv_heads=4,
    d_ff=128,
    vocab_size=320,
    norm_eps=1e-6,
    mla=True,
    kv_lora_rank=32,
    qk_nope_head_dim=16,
    qk_rope_head_dim=8,
    v_head_dim=16,
    rope_scaling=YARN,
    num_experts=8,
    num_experts_per_tok=2,
    num_shared_experts=1,
    moe_d_ff=48,
    norm_topk_prob=False,
    first_dense_layers=1,
    photonic_exclude=HEAD_DIGITAL,
)

ARCH = make_arch(
    "deepseek-v2-lite-16b", "moe", FULL, SMOKE,
    skip_shapes=("long_500k",),
    notes="MLA compressed KV cache (c_kv 512 + rope 64 per token); "
    "long_500k skipped: full attention.",
)
