"""Dense Qwen decoders in the program's terms (``model_type`` qwen3, and
qwen2 through ``qwen2.py``): the ``dense`` family, RMSNorm, grouped-query
attention with rotary embeddings, a SwiGLU feed-forward network; q/k/v
biases where ``attention_bias`` (Qwen2), per-head q/k norms where
``qk_norm`` (Qwen3)."""
from typing import Dict

from repro.configs.base import ModelConfig

#: Weight rules beyond ``model.py``'s common ones: a dense decoder has none.
WEIGHT_RULES: Dict = {}


def program_config(name: str, hf: Dict) -> ModelConfig:
    """The program's config for one model of the configuration file."""
    heads = hf["num_attention_heads"]
    return ModelConfig(
        name=name, family="dense",
        num_layers=hf["num_hidden_layers"], d_model=hf["hidden_size"],
        num_heads=heads, num_kv_heads=hf["num_key_value_heads"],
        head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
        d_ff=hf["intermediate_size"], vocab_size=hf["vocab_size"],
        qkv_bias=bool(hf["attention_bias"]), qk_norm=bool(hf["qk_norm"]),
        rope_theta=float(hf["rope_theta"]),
        tie_embeddings=bool(hf["tie_word_embeddings"]),
        norm_eps=float(hf["rms_norm_eps"]), source=hf["source"])
