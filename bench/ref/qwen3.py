"""Dense Qwen decoders for the plain reference (``model_type`` qwen3, and
qwen2 through ``qwen2.py``), and the sizes and counts ``work.py`` reads.

A layer: RMSNorm, the shared attention (``reference.attention``: q/k/v
biases and q/k norms as the weights and ``qk_norm`` say), RMSNorm, the
SwiGLU feed-forward network. FLOPs count 2 per multiply-add; the per-token
closed form is a copy of ``repro.roofline.flops_analytic`` (dense,
full-attention terms): q/k/v/o projections, scores and attention-weighted
values over the keys attended, and the SwiGLU network; the unembedding is
``2 * d * vocab`` per token whose logits are needed.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

import jax

from bench.harness.reference import mm, rms_norm


def block(hf: Dict, lp: Dict, x: jax.Array, attend, lower: bool):
    """One decoder layer over the residual stream ``x`` (W, d)."""
    eps = hf["rms_norm_eps"]
    o, kv = attend(lp["attn"], rms_norm(x, lp["norm1"]["scale"], eps))
    x = x + o
    f = lp["ffn"]
    h2 = rms_norm(x, lp["norm2"]["scale"], eps)
    g = jax.nn.silu(mm(h2, f["gate"]["w"], lower))
    u = mm(h2, f["up"]["w"], lower)
    return x + mm(g * u, f["down"]["w"], lower), kv


@dataclass(frozen=True)
class Dims:
    """The sizes the counts need, read from a ``config.json``-style dict."""

    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int

    @classmethod
    def of(cls, hf: Dict) -> "Dims":
        h = hf["num_attention_heads"]
        return cls(hf["num_hidden_layers"], hf["hidden_size"], h,
                   hf["num_key_value_heads"],
                   hf.get("head_dim") or hf["hidden_size"] // h,
                   hf["intermediate_size"], hf["vocab_size"])

    def dense_flops(self) -> float:
        """Per token per layer, attention over the keys excluded."""
        d, hd = self.d, self.head_dim
        return (2 * d * (2 * self.heads * hd + 2 * self.kv_heads * hd)
                + 2 * 3 * d * self.ffn)

    def attn_flops(self, keys: float) -> float:
        """Per query row per layer: scores and values over ``keys`` keys."""
        return 2 * 2 * self.heads * self.head_dim * keys

    def head_flops(self) -> float:
        return 2 * self.d * self.vocab

    def decode_token_flops(self, keys: int, prefix: int = 0) -> float:
        """One decoded token attending ``keys`` own and ``prefix`` fused
        keys, with its logits."""
        return (self.layers * (self.dense_flops()
                               + self.attn_flops(keys + prefix))
                + self.head_flops())

    def prefill_flops(self, start: int, rows: int, prefix: int = 0) -> float:
        """Causal prefill of rows ``[start, start + rows)`` (row i attends
        i + 1 own keys and ``prefix`` fused keys), without logits."""
        keys = rows * start + rows * (rows + 1) / 2 + rows * prefix
        return self.layers * (rows * self.dense_flops()
                              + self.attn_flops(keys))

    def kv_row_bytes(self, itemsize: int) -> int:
        """K and V of one token in one layer."""
        return 2 * self.kv_heads * self.head_dim * itemsize


dims = Dims.of
