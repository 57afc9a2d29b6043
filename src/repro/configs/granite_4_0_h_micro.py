"""granite-4.0-h-micro [mixed] — 36 Mamba-2 and 4 NoPE GQA attention
layers by index, a SwiGLU MLP after every mixer, Granite's embedding,
residual and logits multipliers
[hf:ibm-granite/granite-4.0-h-micro config.json, granitemoehybrid]."""
from repro.configs.base import ModelConfig

_ATTENTION_AT = (5, 15, 25, 35)

CONFIG = ModelConfig(
    name="granite-4.0-h-micro", family="mixed",
    n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8,
    d_ff=8192, vocab_size=100352,
    layer_types=tuple("attention" if i in _ATTENTION_AT else "mamba"
                      for i in range(40)),
    position_embedding="nope", attention_multiplier=0.015625,
    embedding_multiplier=12.0, residual_multiplier=0.22, logits_scaling=8.0,
    ssm_state=128, ssm_head_dim=64, ssm_expand=2, ssm_chunk=256,
    ssm_conv_width=4,
    tie_embeddings=True, rms_eps=1e-5,
    source="https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json",
)
