"""moonlight-16b-a3b — latent attention + DeepSeekMoE.
[hf:moonshotai/Moonlight-16B-A3B config.json; model_type deepseek_v3]

27L d_model=2048 16H; MLA (kv_lora_rank 512, no query latent, qk 128+64,
v 128); layer 0 dense (d_ff 11264), layers 1-26 MoE: 64 routed experts of
1408, top-6, sigmoid scores with a selection bias (noaux_tc, one group),
normalised top-k weights times 2.446, 2 shared experts; vocab 163840
untied; rope_theta 50000; context 8192.
"""
from repro.configs.base import ArchConfig, MoEConfig, register


@register("moonlight-16b-a3b")
def config() -> ArchConfig:
    return ArchConfig(
        name="moonlight-16b-a3b",
        family="moe",
        num_layers=27,
        d_model=2048,
        num_heads=16,
        num_kv_heads=16,
        d_ff=11_264,
        vocab_size=163_840,
        rope_theta=50_000.0,
        norm_eps=1e-5,
        first_k_dense=1,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        moe=MoEConfig(
            num_experts=64,
            top_k=6,
            d_ff_expert=1408,
            num_shared_experts=2,
            score_func="sigmoid",
            routed_scaling_factor=2.446,
            # DeepSeek-V3 §2.1.2 (the config.json gives neither): the
            # sequence-wise balance loss alpha and the bias update speed gamma
            router_aux_loss=1e-4,
            bias_update_rate=1e-3,
        ),
        param_dtype="float32",
        remat_policy="dots",
        source="hf:moonshotai/Moonlight-16B-A3B; hf",
    )
