"""Jitted public wrappers around the Pallas kernels.

Off the TPU (the CPU test backend) the kernels execute in ``interpret=True``
mode — the kernel body runs in Python with real BlockSpec tiling semantics — so
the same call sites work on TPU unchanged. The backend alone decides: on a TPU
the kernels always compile through Mosaic, never the interpreter.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import _NEG as _MASK  # shared mask const
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.fuser_mlp import fuser_mlp_pallas
from repro.kernels.gated_fusion import gated_fusion_pallas


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _seq_tile(S: int, block: int) -> tuple[int, int]:
    """Pick a sequence block size and the padded length it implies.

    Preference order: (1) ``min(block, S)`` when it divides S — no padding;
    (2) a halved power-of-two divisor, but only down to 64 (the old
    ``while S % bs: bs //= 2`` fallback degraded all the way to ``bs = 1``
    for odd/prime S — e.g. an unpadded fused-prefix length — launching an
    S-program grid); (3) otherwise keep a lane-aligned power-of-two block
    and pad the tail instead (callers mask padded keys with ``_MASK`` bias /
    positional masks and un-pad the output).
    """
    bs = min(block, S)
    if S % bs == 0:
        return bs, S
    b = bs
    while b > 64 and S % b:
        b //= 2
    if S % b == 0:
        return b, S
    bs = max(8, min(block, 1 << (S - 1).bit_length()))
    return bs, S + (-S) % bs


def fuser_mlp(mlp_params: dict, x: jax.Array, *, block_t: int = 128) -> jax.Array:
    """Apply one fuser MLP {wN: {w, b}} to x (..., d_in) -> (..., d_out)."""
    lead = x.shape[:-1]
    d_in = x.shape[-1]
    T = math.prod(lead) if lead else 1
    xf = x.reshape(T, d_in)
    # pad T to a block multiple
    bt = min(block_t, max(8, T))
    pad = (-T) % bt
    if pad:
        xf = jnp.concatenate([xf, jnp.zeros((pad, d_in), x.dtype)], 0)
    y = fuser_mlp_pallas(
        xf,
        mlp_params["w1"]["w"], mlp_params["w1"]["b"],
        mlp_params["w2"]["w"], mlp_params["w2"]["b"],
        mlp_params["w3"]["w"], mlp_params["w3"]["b"],
        block_t=bt, interpret=_interpret())
    if pad:
        y = y[:T]
    return y.reshape(*lead, y.shape[-1])


def gated_fusion(k_own, v_own, k_proj, v_proj, gate, *, block_s: int = 256):
    """Gated mix over stacked caches (n, B, Hkv, S, hd) + gate (n,)."""
    n, B, H, S, hd = k_own.shape
    bs, Sp = _seq_tile(S, block_s)
    pad5 = ((0, 0), (0, 0), (0, 0), (0, Sp - S), (0, 0))
    rs = lambda a: jnp.pad(a, pad5).reshape(n, B * H, Sp, hd)
    k, v = gated_fusion_pallas(rs(k_own), rs(v_own), rs(k_proj), rs(v_proj),
                               gate, block_s=bs, interpret=_interpret())
    k = k.reshape(n, B, H, Sp, hd)[..., :S, :]
    v = v.reshape(n, B, H, Sp, hd)[..., :S, :]
    return k, v


def _pad_keys(k, v, bias, S: int, Sp: int):
    """Right-pad k/v (B,Hkv,S,hd) with zero keys and bias (B,S) with _MASK so
    the padded tail carries exactly zero attention mass."""
    if Sp == S:
        return k, v, bias
    pad = Sp - S
    k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    bias = jnp.pad(bias, ((0, 0), (0, pad)), constant_values=_MASK)
    return k, v, bias


def decode_attention(q, k, v, bias, *, block_s: int = 512):
    """Flash decode. q (B,H,hd) with GQA heads, k/v (B,Hkv,S,hd), bias (B,S)."""
    B, H, hd = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, hd)
    S = k.shape[2]
    bs, Sp = _seq_tile(S, block_s)
    k, v, bias = _pad_keys(k, v, bias.astype(jnp.float32), S, Sp)
    out = decode_attention_pallas(qg, k, v, bias, block_s=bs,
                                  interpret=_interpret())
    return out.reshape(B, H, hd)


def banded_attention(q, k, v, *, window: int, block: int = 256):
    """Sliding-window prefill attention, O(S·window). q/k/v (B, H, S, hd)."""
    from repro.kernels.banded_attention import banded_attention_pallas
    B, H, S, hd = q.shape
    blk, Sp = _seq_tile(S, block)
    pad4 = ((0, 0), (0, 0), (0, Sp - S), (0, 0))
    # padded queries land after every real key (sliced off below); padded keys
    # sit at positions > every real query, so causality already masks them
    rs = lambda a: jnp.pad(a, pad4).reshape(B * H, Sp, hd)
    out = banded_attention_pallas(rs(q), rs(k), rs(v), window=window,
                                  block=blk, interpret=_interpret())
    return out.reshape(B, H, Sp, hd)[..., :S, :]


def decode_attention_q8(q, qstack, bias, *, block_s: int = 512):
    """Flash decode over an int8-quantised cache (core/quant.py layout):
    q (B,H,hd); qstack {"k_q","v_q" int8 (B,Hkv,S,hd), "k_scale","v_scale"}."""
    from repro.kernels.decode_attention import decode_attention_q8_pallas
    B, H, hd = q.shape
    Hkv = qstack["k_q"].shape[1]
    G = H // Hkv
    S = qstack["k_q"].shape[2]
    bs, Sp = _seq_tile(S, block_s)
    k_q, v_q, bias = _pad_keys(qstack["k_q"], qstack["v_q"],
                               bias.astype(jnp.float32), S, Sp)
    out = decode_attention_q8_pallas(
        q.reshape(B, Hkv, G, hd), k_q, v_q,
        qstack["k_scale"].astype(jnp.float32),
        qstack["v_scale"].astype(jnp.float32),
        bias, block_s=bs, interpret=_interpret())
    return out.reshape(B, H, hd)


# ------------------------------------------------------------------ paged


def paged_decode_attention(q, k_pool, v_pool, page_map, lengths):
    """Flash decode that walks a paged KV pool in-place (no gathered view).

    q (slots, H, hd) with GQA heads; k_pool/v_pool (num_pages, Hkv,
    page_size, hd); page_map (slots, pages_per_slot) int32 physical page ids
    (num_pages == INVALID_PAGE); lengths (slots,) int32 live tokens per slot.

    Returns ``(out (slots, H, hd), m (slots, H), l (slots, H))`` — the online
    softmax statistics let the caller LSE-merge a fused C2C prefix segment
    (models/attention.merge_attention) without concatenating caches. Rows with
    no live page (evicted slots) return zeros with l == 0.
    """
    from repro.kernels.paged_attention import paged_decode_attention_pallas
    B, H, hd = q.shape
    Hkv = k_pool.shape[1]
    G = H // Hkv
    out, m, l = paged_decode_attention_pallas(
        q.reshape(B, Hkv, G, hd), k_pool, v_pool, page_map, lengths,
        interpret=_interpret())
    return out.reshape(B, H, hd), m.reshape(B, H), l.reshape(B, H)


def ragged_prefill_attention(q, k_pool, v_pool, block_seq, block_pos,
                             block_len, page_map, *, block_q: int = 8):
    """Ragged/varlen causal flash prefill straight over a paged KV pool.

    ``q`` (T, H, hd) is a packed buffer of chunk query tokens — multiple
    variable-length prompts laid back to back at ``block_q`` alignment, no
    bucket padding. Per block of ``block_q`` tokens, ``block_seq`` names the
    ``page_map`` row the block's sequence maps its pages through (-1 = pad
    block), ``block_pos`` its absolute first-token position and ``block_len``
    its live rows. Each query attends causally (absolute positions) over its
    own sequence's pool pages — shared prefix pages, earlier chunks and the
    current chunk (scattered into the pool before this call) alike.

    Returns ``(out (T, H, hd), m (T, H), l (T, H))`` — the online softmax
    statistics let the caller LSE-merge a fused C2C prefix segment
    (models/attention.merge_attention). Rows past a block's ragged tail and
    pad blocks return zeros with l == 0.
    """
    from repro.kernels.prefill_attention import ragged_prefill_attention_pallas
    T, H, hd = q.shape
    if block_q < 1 or T % block_q:
        raise ValueError(f"packed length T={T} is not divisible by "
                         f"block_q={block_q}")
    Hkv = k_pool.shape[1]
    G = H // Hkv
    n_blocks = T // block_q
    # (T, H, hd) -> (n_blocks, Hkv, G*block_q, hd): kernel row r = g*block_q + t
    qb = q.reshape(n_blocks, block_q, Hkv, G, hd).transpose(0, 2, 3, 1, 4)
    qb = qb.reshape(n_blocks, Hkv, G * block_q, hd)
    o, m, l = ragged_prefill_attention_pallas(
        qb, k_pool, v_pool, block_seq, block_pos, block_len, page_map,
        block_q=block_q, interpret=_interpret())
    unpack = lambda a, *tail: (
        a.reshape(n_blocks, Hkv, G, block_q, *tail)
        .transpose(0, 3, 1, 2, *range(4, 4 + len(tail)))
        .reshape(T, H, *tail))
    return unpack(o, hd), unpack(m), unpack(l)


def paged_decode_attention_q8(q, qpool, page_map, lengths):
    """int8-pool twin of :func:`paged_decode_attention`: qpool is
    {"k_q","v_q" int8 (num_pages,Hkv,page_size,hd),
    "k_scale","v_scale" fp32 (num_pages,Hkv,1,hd)} (per-page scales)."""
    from repro.kernels.paged_attention import paged_decode_attention_q8_pallas
    B, H, hd = q.shape
    Hkv = qpool["k_q"].shape[1]
    G = H // Hkv
    out, m, l = paged_decode_attention_q8_pallas(
        q.reshape(B, Hkv, G, hd), qpool["k_q"], qpool["v_q"],
        qpool["k_scale"].astype(jnp.float32),
        qpool["v_scale"].astype(jnp.float32),
        page_map, lengths, interpret=_interpret())
    return out.reshape(B, H, hd), m.reshape(B, H), l.reshape(B, H)
