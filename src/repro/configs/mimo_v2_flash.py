"""MiMo-V2-Flash (309B total, 15B active): window and full attention
layers with different KV head counts, a learnable sink on the window
layers, and 256 sigmoid-routed experts.

Source: https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash/blob/main/config.json
(the language model; the 3 MTP draft modules are not part of the
forward pass and are left out).  ``hybrid_layer_pattern`` gives layer 0
and every sixth layer from 5 on full attention (64 query / 4 KV heads,
RoPE theta 5e6), the rest 128-token window attention (64 query / 8 KV
heads, RoPE theta 1e4, sink).  q/k heads are 192 wide, v heads 128;
``partial_rotary_factor`` 0.334 rotates 64 of the 192 q/k dims.  Layer
0 has a dense SwiGLU MLP of 16384; layers 1-47 route each token to 8 of
256 experts of width 2048 (sigmoid scores, ``noaux_tc`` selection bias,
``norm_topk_prob``, one group, no shared expert).
"""
from repro.config import (AttnKind, AttnKindsConfig, ModelConfig,
                          MoEConfig, RoutingConfig)

#: 0 = full, 1 = window, per layer (``hybrid_layer_pattern``)
PATTERN = (0, 1, 1, 1, 1, 0) + (1, 1, 1, 1, 1, 0) * 7


def config() -> ModelConfig:
    return ModelConfig(
        name="mimo-v2-flash",
        family="moe",
        n_layers=48,
        d_model=4096,
        n_heads=64,
        n_kv_heads=4,
        d_head=192,
        d_ff=16384,
        vocab_size=152576,
        rope_theta=5_000_000.0,
        rms_eps=1e-5,
        tie_embeddings=False,
        attn_kinds=AttnKindsConfig(
            pattern=PATTERN,
            kinds=(AttnKind(n_kv_heads=4, rope_theta=5_000_000.0),
                   AttnKind(n_kv_heads=8, rope_theta=10_000.0, window=128,
                            sink=True)),
            v_head_dim=128,
            rotary_dim=64,             # round(0.334 * 192)
            value_scale=0.707),
        moe=MoEConfig(n_experts=256, top_k=8, expert_ff=2048,
                      first_k_dense=1, first_dense_ff=16384),
        routing=RoutingConfig(),
        source="https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash",
    )
