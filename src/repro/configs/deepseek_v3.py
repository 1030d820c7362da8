"""DeepSeek-V3 (hf:deepseek-ai/DeepSeek-V3 config.json): 256 routed experts
in 8 groups, top-8 within the best 4 groups, sigmoid scores with the
aux-loss-free correction bias (``noaux_tc``), gates normalised and scaled
by 2.5, one shared expert.

What this repository runs of it is the router and its expert-load count
(``WorkloadSpec.from_moe_router``, through ``transformer._moe_cfg``).  Its
multi-head latent attention, multi-token prediction and its
``first_k_dense_replace`` = 3 leading dense layers are not modelled, so
the whole-model paths (``ARCHS``) do not list it.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v3", family="moe",
    num_layers=61, d_model=7168, num_heads=128, num_kv_heads=128,
    head_dim=128, d_ff=18432, d_expert=2048, num_experts=256, top_k=8,
    num_shared_experts=1, vocab_size=129280, tie_embeddings=False,
    rope_theta=1e4,
    moe_scoring="sigmoid", moe_n_group=8, moe_topk_group=4,
    moe_routed_scaling_factor=2.5,
)
