"""Pallas TPU kernel: GQA flash-decode attention that walks a *paged* KV pool
in-place — the serving hot loop when the engine runs ``paged=True``.

The paged ``models/cache.SlotTable`` keeps attention K/V in a shared pool of
fixed-size pages, ``(num_pages, Hkv, page_size, hd)`` per layer, with each slot
owning an ordered ``page_map`` row of physical page ids. The previous decode
path gathered every slot's pages into a contiguous ``dense_view()`` each step —
O(slots · max_seq) HBM traffic that grows with the *capacity* of the table, not
with the tokens actually cached. This kernel removes that term: the page map
and per-slot lengths ride in as **scalar-prefetch** operands, the pools stay in
HBM (``memory_space=ANY``), and the kernel DMAs the pages it needs itself.

Grid: ``(slots,)``. One step serves one slot and all of its ``Hkv`` kv heads;
q rows are the ``G = H/Hkv`` grouped query heads of each kv head. Inside the
step a ``fori_loop`` with a dynamic trip count runs over the slot's *live page
blocks* only, ``ceil(ceil(len / page_size) / P)`` of them, each ``P``
consecutive page-map entries. A page holds all kv heads contiguously, so each
live, mapped page of a block is one async copy per pool into a
double-buffered VMEM block ``(2, P, Hkv, page_size, hd)``; block ``i + 1``'s
copies are started before block ``i`` is computed. ``INVALID_PAGE`` entries
and entries past the length start no copy, and a block that copied nothing is
not computed, so an idle slot (length 1, all pages INVALID) or a length-0 slot
costs a few scalar reads. Per step the kernel therefore fetches exactly the
pages that hold live tokens: O(Σ_slots ceil(len_s / page_size)) pages.

``P`` comes from the shape alone (:func:`block_pages`): the largest number of
pages, at most ``pages_per_slot``, whose K block stays within
``BLOCK_BYTES`` (16 pages at Qwen3-0.6B's widths in bf16). The page map is
padded with INVALID to a multiple of ``P``. Heads narrower than 128 lanes are
zero-padded to 128 first, pool included: Mosaic copies a page only when its
lanes are whole.

Per block and kv head the online-softmax recurrence runs over ``P ·
page_size`` keys, masking a partial final page and INVALID pages inside the
length. ``q @ k`` takes the operands' common dtype (bf16 products are exact in
its f32 accumulator); the softmax statistics and ``p @ v`` are float32. The
finish is hardened: a row that saw no key (an evicted slot — all pages
INVALID) emits *zeros* with ``l = 0``, never uniform attention over stale
memory. Alongside the normalised output the kernel returns its (m, l)
statistics so the caller can LSE-merge a fused C2C prefix segment without
ever concatenating it into the paged cache (models/attention
.decode_forward_paged).

An int8-KV variant shares the kernel: pages are stored quantised with
per-(page, head, dim) fp32 scales, which are DMA'd alongside their pages and
applied in VMEM, halving pool HBM traffic again.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import _NEG  # one shared mask constant

BLOCK_BYTES = 512 * 1024  # most K bytes one page block holds in VMEM


def _lanes(head_dim: int) -> int:
    """Head dim as the kernel holds it: whole 128-lane vregs."""
    return -(-head_dim // 128) * 128


def block_pages(pool_shape, itemsize: int, pages_per_slot: int) -> int:
    """P, the page-map entries one block of the paged kernel covers: the
    largest count ``<= pages_per_slot`` whose K pages fit in BLOCK_BYTES
    (16 pages of 8 bf16 heads x 16 tokens x 128 dims)."""
    _, hkv, page_size, hd = pool_shape
    page_bytes = hkv * page_size * _lanes(hd) * itemsize
    return max(1, min(pages_per_slot, BLOCK_BYTES // page_bytes))


def _kernel(pm_ref, len_ref, q_ref, *refs, page_size: int, num_pages: int,
            block_pages: int, n_pools: int, scale: float):
    """``refs``: the pools in HBM (k, v[, k_scale, v_scale]), then o, m, l,
    one VMEM block buffer per pool, the DMA semaphores and the m/l/acc
    scratch."""
    pools = refs[:n_pools]
    o_ref, m_out, l_out = refs[n_pools:n_pools + 3]
    bufs = refs[n_pools + 3:2 * n_pools + 3]
    sem, m_ref, l_ref, acc_ref = refs[2 * n_pools + 3:]
    quantized = n_pools == 4
    P, n_keys = block_pages, block_pages * page_size
    Hkv, hd = q_ref.shape[1], q_ref.shape[3]
    s = pl.program_id(0)
    length = len_ref[s]
    live_pages = (length + page_size - 1) // page_size
    n_blocks = (live_pages + P - 1) // P

    @pl.when(s == 0)
    def _zero_buffers():
        # A key that no copy filled is masked, so its p is exactly 0; 0 * v
        # is 0 only if v is finite. Copies only ever bring pool data, so
        # zeroing once before the first copy suffices: the grid runs in
        # order on one core ("arbitrary" below).
        for buf in bufs:
            buf[...] = jnp.zeros_like(buf)

    def each_live_page(blk, slot, act):
        """Apply ``act`` (start or wait) to the copies of every mapped page of
        block ``blk`` that lies inside the length; returns how many."""
        first = blk * P

        def one(j, n):
            page = pm_ref[s, first + j]
            mapped = page < num_pages

            @pl.when(mapped)
            def _copy():
                for i, (src, dst) in enumerate(zip(pools, bufs)):
                    act(pltpu.make_async_copy(src.at[page], dst.at[slot, j],
                                              sem.at[i, slot]))

            return n + mapped.astype(jnp.int32)

        return jax.lax.fori_loop(0, jnp.minimum(P, live_pages - first), one,
                                 jnp.int32(0))

    def attend(blk, slot):
        col = jax.lax.broadcasted_iota(jnp.int32, (1, n_keys), 1)
        key_page = col // page_size

        def mark(j, ok):  # INVALID pages inside the length stay masked
            mapped = (pm_ref[s, blk * P + j] < num_pages).astype(jnp.int32)
            return jnp.where(key_page == j, mapped, ok)

        ok = jax.lax.fori_loop(0, P, mark, jnp.zeros((1, n_keys), jnp.int32))
        live = (ok > 0) & (blk * n_keys + col < length)  # + partial last page
        for h in range(Hkv):
            k, v = bufs[0][slot, :, h], bufs[1][slot, :, h]  # (P, pg, hd)
            if quantized:
                k = k.astype(jnp.float32) * bufs[2][slot, :, h]
                v = v.astype(jnp.float32) * bufs[3][slot, :, h]
            q = q_ref[0, h]  # (G, hd)
            dt = jnp.promote_types(q.dtype, k.dtype)
            scores = jax.lax.dot_general(
                q.astype(dt), k.reshape(n_keys, hd).astype(dt),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            scores = jnp.where(live, scores, _NEG)  # (G, n_keys)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(scores - m_new)
            l_ref[h] = l_ref[h] * alpha + p.sum(axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p, v.reshape(n_keys, hd).astype(jnp.float32),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(n_blocks > 0)
    def _prime():
        each_live_page(0, 0, lambda c: c.start())

    def block(blk, carry):
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _prefetch():
            each_live_page(blk + 1, 1 - slot, lambda c: c.start())

        copied = each_live_page(blk, slot, lambda c: c.wait())

        @pl.when(copied > 0)  # a block with no mapped page adds nothing
        def _attend():
            attend(blk, slot)

        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)

    # hardened: a row that saw no key still has m == _NEG; emit zeros so
    # stale memory can never leak past the slot mask.
    seen = m_ref[...] > _NEG / 2  # (Hkv, G, 1)
    o = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = jnp.where(seen, o, 0.0).astype(o_ref.dtype)
    m_out[0] = m_ref[...]
    l_out[0] = jnp.where(seen, l_ref[...], 0.0)


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


def _paged_call(q, pools, page_map, lengths, *, interpret: bool):
    """Shared pallas_call plumbing for the fp32/bf16 and int8 variants:
    ``pools`` is (k, v) or (k_q, v_q, k_scale, v_scale), all shaped
    (num_pages, Hkv, ·, hd). Returns (o, m, l) with m and l (slots, Hkv, G).

    m and l leave the kernel as (slots, Hkv, G, 1): Mosaic needs a block's
    last two dims to be (8, 128)-aligned or whole, and a (G,) block of a
    (..., G) array is neither for G < 128."""
    _validate(q, pools[0].shape, page_map, lengths)
    slots, Hkv, G, head_dim = q.shape
    width = page_map.shape[1]
    P = block_pages(pools[0].shape, pools[0].dtype.itemsize, width)
    # Mosaic slices a page out of the pool only when its lanes are whole:
    # narrow heads are zero-padded to 128 lanes (zeros add nothing to q @ k
    # and fill only the dropped lanes of p @ v), at the cost of a pool copy.
    hd = _lanes(head_dim)
    if hd != head_dim:
        lanes = ((0, 0),) * 3 + ((0, hd - head_dim),)
        q = jnp.pad(q, lanes)
        pools = tuple(jnp.pad(p, lanes) for p in pools)
    num_pages, _, page_size, _ = pools[0].shape
    page_map = jnp.pad(page_map.astype(jnp.int32),
                       ((0, 0), (0, -width % P)), constant_values=num_pages)

    def whole_slot(s, pm, ln):
        return (s, 0, 0, 0)

    heads = pl.BlockSpec((1, Hkv, G, hd), whole_slot)
    stat = pl.BlockSpec((1, Hkv, G, 1), whole_slot)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots,),
        in_specs=[heads] + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
        out_specs=[heads, stat, stat],
        scratch_shapes=[pltpu.VMEM((2, P) + p.shape[1:], p.dtype)
                        for p in pools] + [
            pltpu.SemaphoreType.DMA((len(pools), 2)),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),   # running max m
            pltpu.VMEM((Hkv, G, 1), jnp.float32),   # normaliser l
            pltpu.VMEM((Hkv, G, hd), jnp.float32),  # output accumulator
        ],
    )
    o, m, l = pl.pallas_call(
        functools.partial(_kernel, page_size=page_size, num_pages=num_pages,
                          block_pages=P, n_pools=len(pools),
                          scale=head_dim ** -0.5),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((slots, Hkv, G, hd), q.dtype),
            jax.ShapeDtypeStruct((slots, Hkv, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((slots, Hkv, G, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_map, lengths.astype(jnp.int32), q, *pools)
    return o[..., :head_dim], m[..., 0], l[..., 0]


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
    return _paged_call(q, (k_pool, v_pool), page_map, lengths,
                       interpret=interpret)


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
    return _paged_call(q, (k_q, v_q, k_scale, v_scale), page_map, lengths,
                       interpret=interpret)
