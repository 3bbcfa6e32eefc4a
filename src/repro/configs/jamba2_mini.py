"""AI21-Jamba2-Mini [hybrid]: 32L d=4096, blocks of 8 layers of 7 Mamba-1
(state 16, conv 4, dt rank 256, expand 2, RMSNorms on dt/B/C) and one GQA
attention layer (32H, kv=8, head 128, no rotary embedding); a 16-expert
top-2 MoE (width 14336, softmax router, weights not renormalised) on odd
layers and a dense SwiGLU of the same width on even ones; vocab 65536
[huggingface.co/ai21labs/AI21-Jamba2-Mini config.json; transformers'
models/jamba]."""
from typing import Any, Dict, Optional, Tuple

from repro.configs.base import ModelConfig, MoEConfig, SSMConfig

# config.json, the keys that give the shape
HF_CONFIG: Dict[str, Any] = {
    "attn_layer_offset": 4, "attn_layer_period": 8,
    "expert_layer_offset": 1, "expert_layer_period": 2,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 14336,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
    "mamba_dt_rank": 256, "mamba_expand": 2, "mamba_proj_bias": False,
    "num_attention_heads": 32, "num_experts": 16, "num_experts_per_tok": 2,
    "num_hidden_layers": 32, "num_key_value_heads": 8, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "vocab_size": 65536,
}


def from_hf(c: Dict[str, Any], name: str = "jamba2-mini",
            held: Optional[Tuple[int, int]] = None) -> ModelConfig:
    """A Jamba ModelConfig from config.json's keys.  `held` is the range of
    the router's experts this layer holds (default all); a model with one
    expert has dense FFNs only, as transformers builds it."""
    if c["hidden_act"] != "silu" or c["mamba_proj_bias"] \
            or not c["mamba_conv_bias"]:
        raise ValueError("only SiLU, unbiased projections and a biased conv "
                         "are modelled")
    moe = None
    if c["num_experts"] > 1:
        moe = MoEConfig(num_experts=c["num_experts"],
                        top_k=c["num_experts_per_tok"],
                        expert_d_ff=c["intermediate_size"],
                        layer_period=c["expert_layer_period"],
                        layer_offset=c["expert_layer_offset"],
                        renormalize=False, dropless=True,
                        held=tuple(held or ()))
    d, heads = c["hidden_size"], c["num_attention_heads"]
    return ModelConfig(
        name=name, family="hybrid", num_layers=c["num_hidden_layers"],
        d_model=d, num_heads=heads, num_kv_heads=c["num_key_value_heads"],
        head_dim=d // heads, d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], attention="gqa", use_rope=False,
        attn_layer_period=c["attn_layer_period"],
        attn_layer_offset=c["attn_layer_offset"], moe=moe,
        ssm=SSMConfig(state_dim=c["mamba_d_state"],
                      conv_kernel=c["mamba_d_conv"],
                      expand=c["mamba_expand"], dt_rank=c["mamba_dt_rank"],
                      inner_norms=True),
        tie_embeddings=c["tie_word_embeddings"], norm_eps=c["rms_norm_eps"],
        source="huggingface.co/ai21labs/AI21-Jamba2-Mini (config.json)")


CONFIG = from_hf(HF_CONFIG)
