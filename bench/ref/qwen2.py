"""Qwen2: the dense Qwen decoder of ``qwen3.py``, whose shared attention
adds q/k/v biases where the weights hold them."""
from bench.ref.qwen3 import Dims, block, dims  # noqa: F401
