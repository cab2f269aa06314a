"""deepseek-v2-lite-16b [moe], after the published config
(huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json;
arXiv:2405.04434): 27 layers, d_model 2048, 16 heads of MLA with no
q_lora, kv_lora_rank 512, qk_nope 128 + qk_rope 64, v_head 128; YaRN
rope scaling (factor 40 over 4,096 original positions, beta_fast 32,
beta_slow 1, mscale = mscale_all_dim = 0.707); one leading dense SwiGLU
layer of width 10,944 (``d_ff``), then 26 MoE layers of 64 routed
experts of width 1,408 (``moe_d_ff``), softmax scoring, greedy top-6
without renormalisation (``norm_topk_prob`` false,
``routed_scaling_factor`` 1), and 2 shared experts; vocabulary 102,400,
head not tied.  DeepSeek's interleaved rope layout is the repository's
two-halves rope after a fixed permutation of the rope columns of the
query and key projections."""

from repro.models.common import ModelConfig

CONFIG = ModelConfig(
    arch="deepseek-v2-lite-16b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10_944,            # the single leading dense-FFN layer
    moe_d_ff=1_408,         # each routed and shared expert
    n_experts=64,
    n_shared_experts=2,
    top_k=6,
    norm_topk_prob=False,
    first_dense_layers=1,
    vocab=102_400,
    norm_eps=1e-6,
    attn_kind="mla",
    kv_lora_rank=512,
    qk_rope_dim=64,
    qk_nope_dim=128,
    v_head_dim=128,
    rope_theta=10_000.0,
    yarn_factor=40.0,
    yarn_original_max_pos=4_096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
)
