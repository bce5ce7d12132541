"""Qwen2: the dense Qwen decoder of ``qwen3.py``, which reads q/k/v biases
and q/k norms from the configuration's ``attention_bias`` and ``qk_norm``."""
from bench.arch.qwen3 import WEIGHT_RULES, program_config  # noqa: F401
