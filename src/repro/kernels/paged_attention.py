"""Pallas TPU kernel: GQA flash-decode attention that walks a *paged* KV pool
in-place — the serving hot loop when the engine runs ``paged=True``.

The paged ``models/cache.SlotTable`` keeps attention K/V in a shared pool of
fixed-size pages, ``(num_pages, Hkv, page_size, hd)`` per layer, with each slot
owning an ordered ``page_map`` row of physical page ids. The previous decode
path gathered every slot's pages into a contiguous ``dense_view()`` each step —
O(slots · max_seq) HBM traffic that grows with the *capacity* of the table, not
with the tokens actually cached. This kernel removes that term: the page map
and per-slot lengths ride in as **scalar-prefetch** operands, the kv BlockSpec
index map dereferences ``page_map[slot, page]`` directly (so the DMA engine
fetches physical pages straight from the pool), and unallocated
(``INVALID_PAGE``) or beyond-length pages are skipped with ``pl.when`` instead
of being gathered and masked. Per step the kernel reads exactly the pages that
hold live tokens: O(Σ_slots ceil(len_s / page_size) · page_size).

Online-softmax recurrence over the sequential innermost page dimension (same
scratch discipline as decode_attention.py), with the hardened finish: a row
whose every page was skipped (an evicted slot — all pages INVALID) emits
*zeros*, never uniform attention over uninitialized pool memory. Alongside the
normalised output the kernel returns its (m, l) statistics so the caller can
LSE-merge a fused C2C prefix segment without ever concatenating it into the
paged cache (models/attention.decode_forward_paged).

Grid: (slots, kv_heads, pages_per_slot); q rows are the G = H/Hkv grouped
query heads for that kv head. An int8-KV variant mirrors _kernel_q8: pages are
stored quantised with per-(page, head, dim) fp32 scales and dequantised in
VMEM, halving pool HBM traffic again.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import _NEG  # one shared mask constant


def _kernel(pm_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_out, l_out,
            m_ref, l_ref, acc_ref, *, page_size: int, num_pages: int):
    s_idx = pl.program_id(0)
    p_idx = pl.program_id(2)
    n_p = pl.num_programs(2)

    @pl.when(p_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[s_idx]
    page = pm_ref[s_idx, p_idx]
    # INVALID_PAGE (== num_pages) or a page past the live length: skip the
    # block entirely — no gather, no masking, no HBM read is consumed by it.
    live = (page < num_pages) & (p_idx * page_size < length)

    @pl.when(live)
    def _accum():
        q = q_ref[0, 0].astype(jnp.float32)  # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (page_size, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        t = p_idx * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        scores = q @ k.T * (q.shape[-1] ** -0.5)  # (G, page_size)
        scores = jnp.where(t < length, scores, _NEG)  # partial final page
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + p @ v
        m_ref[...] = m_new

    @pl.when(p_idx == n_p - 1)
    def _finish():
        # hardened: a fully-skipped row (every page INVALID/out-of-length)
        # still has m == _NEG; emit zeros so garbage can never leak past the
        # slot mask (p = exp(0) = 1 uniform attention otherwise).
        seen = m_ref[...] > _NEG / 2  # (G, 1)
        o = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = jnp.where(seen, o, 0.0).astype(o_ref.dtype)
        m_out[0, 0] = m_ref[...]
        l_out[0, 0] = jnp.where(seen, l_ref[...], 0.0)


def _kernel_q8(pm_ref, len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
               m_out, l_out, m_ref, l_ref, acc_ref, *, page_size: int,
               num_pages: int):
    """int8-pool variant: pages arrive as int8 blocks and are dequantised in
    VMEM with per-(page, head, dim) fp32 scales — pool HBM traffic halves."""
    s_idx = pl.program_id(0)
    p_idx = pl.program_id(2)
    n_p = pl.num_programs(2)

    @pl.when(p_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[s_idx]
    page = pm_ref[s_idx, p_idx]
    live = (page < num_pages) & (p_idx * page_size < length)

    @pl.when(live)
    def _accum():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0].astype(jnp.float32)
        t = p_idx * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        scores = q @ k.T * (q.shape[-1] ** -0.5)
        scores = jnp.where(t < length, scores, _NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + p @ v
        m_ref[...] = m_new

    @pl.when(p_idx == n_p - 1)
    def _finish():
        seen = m_ref[...] > _NEG / 2
        o = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = jnp.where(seen, o, 0.0).astype(o_ref.dtype)
        m_out[0, 0] = m_ref[...]
        l_out[0, 0] = jnp.where(seen, l_ref[...], 0.0)


def _validate(q, pool_shape, page_map, lengths):
    slots, Hkv_q, G, hd = q.shape
    num_pages, Hkv, page_size, hd_p = pool_shape
    if Hkv != Hkv_q or hd != hd_p:
        raise ValueError(
            f"q {q.shape} does not match pool {pool_shape}: expected "
            f"(slots, {Hkv}, G, {hd_p})")
    if page_map.ndim != 2 or page_map.shape[0] != slots:
        raise ValueError(
            f"page_map {page_map.shape} must be (slots={slots}, pages_per_slot)")
    if lengths.shape != (slots,):
        raise ValueError(f"lengths {lengths.shape} must be (slots={slots},)")


def _paged_call(kernel_fn, q, pool_shape, pps, *, n_scales: int,
                interpret: bool):
    """Shared pallas_call plumbing for the fp32/bf16 and int8 variants: the
    scalar-prefetch grid spec (page-map-dereferencing kv index maps), the
    (o, m, l) out specs/shapes and the online-softmax scratch.

    m and l leave the kernel as (slots, Hkv, G, 1): Mosaic needs a block's
    last two dims to be (8, 128)-aligned or whole, and a (G,) block of a
    (..., G) array is neither for G < 128. The callers drop the unit axis."""
    slots, Hkv, G, hd = q.shape
    num_pages, _, page_size, _ = pool_shape

    def kv_index(s, h, p, pm, ln):
        # dereference the page map at DMA-issue time (scalar prefetch);
        # INVALID ids clamp to a real page whose block the kernel skips
        return (jnp.minimum(pm[s, p], num_pages - 1), h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots, Hkv, pps),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda s, h, p, pm, ln: (s, h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, hd), kv_index),
            pl.BlockSpec((1, 1, page_size, hd), kv_index),
        ] + [pl.BlockSpec((1, 1, 1, hd), kv_index)] * n_scales,
        out_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda s, h, p, pm, ln: (s, h, 0, 0)),
            pl.BlockSpec((1, 1, G, 1), lambda s, h, p, pm, ln: (s, h, 0, 0)),
            pl.BlockSpec((1, 1, G, 1), lambda s, h, p, pm, ln: (s, h, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),   # running max m
            pltpu.VMEM((G, 1), jnp.float32),   # normaliser l
            pltpu.VMEM((G, hd), jnp.float32),  # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(kernel_fn, page_size=page_size,
                          num_pages=num_pages),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((slots, Hkv, G, hd), q.dtype),
            jax.ShapeDtypeStruct((slots, Hkv, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((slots, Hkv, G, 1), jnp.float32),
        ],
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas(
    q: jax.Array,  # (slots, Hkv, G, hd) — grouped query heads
    k_pool: jax.Array,  # (num_pages, Hkv, page_size, hd)
    v_pool: jax.Array,
    page_map: jax.Array,  # (slots, pages_per_slot) int32; num_pages = INVALID
    lengths: jax.Array,  # (slots,) int32 live tokens per slot
    *,
    interpret: bool = False,
):
    """Returns (o (slots,Hkv,G,hd), m (slots,Hkv,G), l (slots,Hkv,G))."""
    _validate(q, k_pool.shape, page_map, lengths)
    call = _paged_call(_kernel, q, k_pool.shape, page_map.shape[1],
                       n_scales=0, interpret=interpret)
    o, m, l = call(page_map.astype(jnp.int32), lengths.astype(jnp.int32),
                   q, k_pool, v_pool)
    return o, m[..., 0], l[..., 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_q8_pallas(
    q: jax.Array,  # (slots, Hkv, G, hd)
    k_q: jax.Array,  # (num_pages, Hkv, page_size, hd) int8
    v_q: jax.Array,  # int8
    k_scale: jax.Array,  # (num_pages, Hkv, 1, hd) fp32
    v_scale: jax.Array,
    page_map: jax.Array,  # (slots, pages_per_slot) int32
    lengths: jax.Array,  # (slots,) int32
    *,
    interpret: bool = False,
):
    """int8-pool twin of :func:`paged_decode_attention_pallas`."""
    _validate(q, k_q.shape, page_map, lengths)
    call = _paged_call(_kernel_q8, q, k_q.shape, page_map.shape[1],
                       n_scales=2, interpret=interpret)
    o, m, l = call(page_map.astype(jnp.int32), lengths.astype(jnp.int32),
                   q, k_q, v_q, k_scale, v_scale)
    return o, m[..., 0], l[..., 0]
