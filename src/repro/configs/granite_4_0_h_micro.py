"""granite-4.0-h-micro [hf:ibm-granite/granite-4.0-h-micro] — 40 layers,
Mamba-2 and GQA attention interleaved 9:1 (attention at 5, 15, 25, 35),
each followed by a SwiGLU MLP; no positional encoding, scaled residual
branches, embedding and logits (model_type granitemoehybrid)."""
from repro.config import ModelConfig, register_arch

N_LAYERS = 40
ATTENTION_AT = (5, 15, 25, 35)


@register_arch("granite_4_0_h_micro")
def granite_4_0_h_micro() -> ModelConfig:
    return ModelConfig(
        name="granite-4.0-h-micro",
        arch_type="hybrid",
        source="[hf:ibm-granite/granite-4.0-h-micro]",
        n_layers=N_LAYERS,
        layer_pattern=tuple("attention" if i in ATTENTION_AT else "mamba"
                            for i in range(N_LAYERS)),
        d_model=2048,
        n_heads=32,
        n_kv_heads=8,
        head_dim=64,
        d_ff=8192,              # shared_intermediate_size; no experts
        vocab_size=100352,
        tie_embeddings=True,
        norm_eps=1e-5,
        attention_mode="full",
        position_embedding="nope",
        attention_multiplier=0.015625,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        logits_scaling=8.0,
        ssm_state=128,          # mamba_d_state; one B/C group
        ssm_heads=64,           # mamba_n_heads
        ssm_head_dim=64,        # mamba_d_head: d_inner 4096 = 2 x 2048
        ssm_expand=2,
        ssm_conv=4,
        ssm_chunk=256,
    )
