"""Operations and bytes the served work needs, and the chip's peaks.

These are the benchmark's own counts, from the configuration file's sizes,
so no change to the program can move them. FLOPs count 2 per multiply-add.
A model's own counts (its layers' FLOPs per token, its K/V row) come from
its architecture's ``dims(hf)`` (``bench/ref/<model_type>.py``); the fuser,
the transmit side and the kernels' needs are counted here from those.
"""
from __future__ import annotations

import math
from typing import Dict, Protocol

#: Published peaks per chip, keyed by ``jax.Device.device_kind`` (a copy of
#: ``repro.hw.PEAKS``). A chip missing here is an error, not a default.
PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": {  # TPU v5e
        "bf16_flops": 197e12,
        "hbm_bw": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def peaks(device_kind: str) -> Dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


class Dims(Protocol):
    """What the counts read of one model: an architecture's ``dims(hf)``."""

    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int

    def dense_flops(self) -> float:
        """Per token per layer, attention over the keys excluded."""

    def attn_flops(self, keys: float) -> float:
        """Per query row per layer: scores and values over ``keys`` keys."""

    def head_flops(self) -> float:
        """The logits of one token."""

    def decode_token_flops(self, keys: int, prefix: int = 0) -> float:
        """One decoded token attending ``keys`` own and ``prefix`` fused
        keys, with its logits."""

    def prefill_flops(self, start: int, rows: int, prefix: int = 0) -> float:
        """Causal prefill of rows ``[start, start + rows)``, without
        logits."""

    def kv_row_bytes(self, itemsize: int) -> int:
        """K and V of one token in one layer."""


def fuser_flops(tx: Dims, rx: Dims, tokens: int) -> float:
    """The fuser's three-layer MLP over ``tokens`` transmitted rows, for
    every receiver layer, plus the gate's pooled scoring."""
    d_in = 2 * tx.kv_heads * tx.head_dim
    d_out = 2 * rx.kv_heads * rx.head_dim
    d_h = max(d_in, d_out)
    mlp = 2 * (d_in * d_h + d_h * d_h + d_h * d_out)
    return rx.layers * tokens * mlp + 2 * (d_out * 128 + 128)


def transmit_flops(tx: Dims, rx: Dims, tokens: int) -> float:
    """The transmit side of one C2C request: the transmitter's causal
    prefill of its prompt (no logits needed) and the fuser projection."""
    return tx.prefill_flops(0, tokens) + fuser_flops(tx, rx, tokens)


# ------------------------------------------------------------- kernels


def paged_decode_need(rx: Dims, keys: int, page_size: int,
                      itemsize: int) -> tuple:
    """(flops, bytes) the paged decode kernel needs for one slot in one
    layer: every head scores and weighs ``keys`` keys, reading the live
    pages of K and V once, q once and writing o, m and l once."""
    flops = rx.attn_flops(keys)
    pages = math.ceil(keys / page_size)
    nbytes = (pages * page_size * rx.kv_row_bytes(itemsize)
              + 2 * rx.heads * rx.head_dim * itemsize + 2 * rx.heads * 4)
    return flops, nbytes


def ragged_prefill_need(rx: Dims, start: int, rows: int, page_size: int,
                        itemsize: int) -> tuple:
    """(flops, bytes) the ragged prefill kernel needs for one chunk of
    ``rows`` prompt rows starting at position ``start``, in one layer: the
    causal prefix of each live row, its keys read once."""
    keys = rows * start + rows * (rows + 1) / 2
    flops = rx.attn_flops(keys)
    pages = math.ceil((start + rows) / page_size)
    nbytes = (pages * page_size * rx.kv_row_bytes(itemsize)
              + rows * (2 * rx.heads * rx.head_dim * itemsize
                        + 2 * rx.heads * 4))
    return flops, nbytes


def need_seconds(flops: float, nbytes: float, peak: Dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bw"])
