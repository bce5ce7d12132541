"""Pallas TPU kernel: ragged/varlen causal flash-prefill straight over the
paged KV pool — the chunked-prefill hot path.

Monolithic bucketed prefill pads every prompt in an admission batch to the
bucket length and runs one dense forward over the padded rectangle: a single
long prompt monopolises the device for the whole forward while every decode
slot starves (the long-prompt p99 stall engine_bench measures). This kernel is
the attention half of the fix: *chunks* of multiple variable-length prompts
are packed back to back into one query buffer — block_q-aligned, no bucket
padding — and each query attends, causally, the keys of **its own sequence
only**, read directly from the paged ``models/cache.SlotTable`` pool the
chunk's K/V were just scattered into.

Ragged bookkeeping rides in as **scalar-prefetch** operands (the same
``PrefetchScalarGridSpec`` machinery as kernels/paged_attention.py):

- ``block_seq`` (n_blocks,): which packed sequence each query block belongs
  to (a row of ``page_map``); -1 marks a padding block (skipped entirely).
- ``block_pos`` (n_blocks,): absolute position of the block's first query
  token — the chunk's ``pos_offset`` plus its offset within the chunk.
- ``block_len`` (n_blocks,): live query rows in the block (ragged tail).
- ``page_map`` (rows, pages_per_slot): physical page ids per sequence,
  ``num_pages`` == INVALID; the kv BlockSpec index map dereferences
  ``page_map[block_seq[b], p]`` at DMA-issue time, so the DMA engine fetches
  exactly the pages that hold the sequence's live tokens.

Because a chunk's own K/V are written to their pages *before* the kernel
runs, causality (``k_pos <= q_pos``) uniformly covers three key segments with
one rule: radix-shared prefix pages, pages written by earlier chunks, and the
current chunk itself. Pages past the last query position are skipped with
``pl.when`` — a chunk at offset P reads O(P + chunk) keys, not O(max_seq).

Online-softmax recurrence over the sequential innermost page dimension with
the hardened finish (masked tails and dead blocks emit exact zeros, never
uniform attention over uninitialized pool memory). Alongside the normalised
output the kernel returns its (m, l) statistics so the caller can LSE-merge a
fused C2C prefix segment (models/attention.prefill_chunk_forward) without
concatenating it into the paged cache.

Grid: (n_blocks, kv_heads, pages_per_slot); q rows are the G = H/Hkv grouped
query heads × block_q chunk tokens for that kv head (row r = g·block_q + t).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import _NEG  # one shared mask constant


def _kernel(seq_ref, pos_ref, len_ref, pm_ref, q_ref, k_ref, v_ref,
            o_ref, m_out, l_out, m_ref, l_ref, acc_ref, *,
            page_size: int, num_pages: int, block_q: int):
    b_idx = pl.program_id(0)
    p_idx = pl.program_id(2)
    n_p = pl.num_programs(2)

    @pl.when(p_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seq = seq_ref[b_idx]
    base = pos_ref[b_idx]
    nq = len_ref[b_idx]
    page = pm_ref[jnp.maximum(seq, 0), p_idx]
    # a padding block (seq == -1), an INVALID page, or a page entirely past
    # the block's last query position: skip — no HBM read is consumed by it
    live = (seq >= 0) & (page < num_pages) & (p_idx * page_size < base + nq)

    @pl.when(live)
    def _accum():
        q = q_ref[0, 0].astype(jnp.float32)  # (G*block_q, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (page_size, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        rows = jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], 1), 0)
        t = rows % block_q                   # query index within the chunk
        q_pos = base + t                     # absolute query position
        k_pos = p_idx * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        scores = q @ k.T * (q.shape[-1] ** -0.5)  # (G*block_q, page_size)
        # causal against absolute positions + ragged tail rows masked out
        valid = (k_pos <= q_pos) & (t < nq)
        scores = jnp.where(valid, scores, _NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + p @ v
        m_ref[...] = m_new

    @pl.when(p_idx == n_p - 1)
    def _finish():
        # hardened: rows past the ragged tail and fully-dead blocks still
        # have m == _NEG; emit exact zeros so garbage can never leak past the
        # packing mask (p = exp(0) = 1 uniform attention otherwise). A live
        # row always sees at least its own key (written before the call).
        seen = m_ref[...] > _NEG / 2  # (G*block_q, 1)
        o = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = jnp.where(seen, o, 0.0).astype(o_ref.dtype)
        m_out[0, 0] = m_ref[...]
        l_out[0, 0] = jnp.where(seen, l_ref[...], 0.0)


def _validate(q, pool_shape, block_seq, block_pos, block_len, page_map):
    n_blocks, Hkv_q, gbq, hd = q.shape
    num_pages, Hkv, page_size, hd_p = pool_shape
    if Hkv != Hkv_q or hd != hd_p:
        raise ValueError(
            f"q {q.shape} does not match pool {pool_shape}: expected "
            f"(n_blocks, {Hkv}, G*block_q, {hd_p})")
    for name, arr in (("block_seq", block_seq), ("block_pos", block_pos),
                      ("block_len", block_len)):
        if arr.shape != (n_blocks,):
            raise ValueError(
                f"{name} {arr.shape} must be (n_blocks={n_blocks},)")
    if page_map.ndim != 2:
        raise ValueError(
            f"page_map {page_map.shape} must be (rows, pages_per_slot)")


def _ragged_call(q, pool_shape, pps, *, block_q: int, interpret: bool):
    """The pallas_call plumbing: scalar-prefetch grid spec whose kv index
    maps dereference ``page_map[block_seq[b], p]`` at DMA-issue time, the
    (o, m, l) out specs/shapes and the online-softmax scratch. m and l
    carry a trailing unit axis for Mosaic's block-shape rule (see
    kernels/paged_attention._paged_call); the caller drops it."""
    n_blocks, Hkv, gbq, hd = q.shape
    num_pages, _, page_size, _ = pool_shape

    def kv_index(b, h, p, bs, bp, bl, pm):
        # dereference the packed sequence's page map (scalar prefetch);
        # dead blocks clamp to row 0 and INVALID ids clamp to a real page
        # whose block the kernel skips
        return (jnp.minimum(pm[jnp.maximum(bs[b], 0), p], num_pages - 1),
                h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_blocks, Hkv, pps),
        in_specs=[
            pl.BlockSpec((1, 1, gbq, hd),
                         lambda b, h, p, bs, bp, bl, pm: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, hd), kv_index),
            pl.BlockSpec((1, 1, page_size, hd), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, gbq, hd),
                         lambda b, h, p, bs, bp, bl, pm: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, gbq, 1),
                         lambda b, h, p, bs, bp, bl, pm: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, gbq, 1),
                         lambda b, h, p, bs, bp, bl, pm: (b, h, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((gbq, 1), jnp.float32),   # running max m
            pltpu.VMEM((gbq, 1), jnp.float32),   # normaliser l
            pltpu.VMEM((gbq, hd), jnp.float32),  # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, page_size=page_size, num_pages=num_pages,
                          block_q=block_q),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_blocks, Hkv, gbq, hd), q.dtype),
            jax.ShapeDtypeStruct((n_blocks, Hkv, gbq, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_blocks, Hkv, gbq, 1), jnp.float32),
        ],
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def ragged_prefill_attention_pallas(
    q: jax.Array,  # (n_blocks, Hkv, G*block_q, hd) — packed query blocks
    k_pool: jax.Array,  # (num_pages, Hkv, page_size, hd)
    v_pool: jax.Array,
    block_seq: jax.Array,  # (n_blocks,) int32 page_map row; -1 = pad block
    block_pos: jax.Array,  # (n_blocks,) int32 absolute first-query position
    block_len: jax.Array,  # (n_blocks,) int32 live query rows (<= block_q)
    page_map: jax.Array,  # (rows, pages_per_slot) int32; num_pages = INVALID
    *,
    block_q: int,
    interpret: bool = False,
):
    """Returns (o (n_blocks,Hkv,G*block_q,hd), m, l (n_blocks,Hkv,G*block_q))."""
    _validate(q, k_pool.shape, block_seq, block_pos, block_len, page_map)
    call = _ragged_call(q, k_pool.shape, page_map.shape[1],
                        block_q=block_q, interpret=interpret)
    o, m, l = call(block_seq.astype(jnp.int32), block_pos.astype(jnp.int32),
                   block_len.astype(jnp.int32), page_map.astype(jnp.int32),
                   q, k_pool, v_pool)
    return o, m[..., 0], l[..., 0]
