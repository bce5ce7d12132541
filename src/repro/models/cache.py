"""Typed decode-state pytrees: KV caches, communicated stacks, fused prefixes,
and the paged slot table.

The cache is the *medium of federation* in this paper (C2C communicates KV
caches), so its layout is a first-class design object. This module defines the
four typed pytrees the whole stack is built on (each registered with
``jax.tree_util`` so it jits/vmaps/scans like any dict, but with a closed,
documented field set):

- :class:`KVCache`   — a model's full decode state (``pos`` + per-layer
  entries). Subsumes the old free functions ``init_cache``/``attn_kv_stack``/
  ``cache_insert_slot``/``cache_evict_slot``/``init_slot_cache``.
- :class:`KVStack`   — the tensor C2C communicates: all attention-layer k/v
  collected into one (n_attn, B, Hkv, S, hd) stack. Subsumes ``concat_kv``.
- :class:`FusedPrefix` — a projected (receiver-space) stack plus its
  attention-logit bias. Subsumes ``empty_fused_stack``/``pad_fused_stack``/
  ``fused_stack_insert_slot``/``extra_kv_layers``.
- :class:`SlotTable` — a *paged* engine slot table: fixed-size KV pages in a
  shared pool plus a per-slot page map, so concurrent slot capacity is bound
  by pages actually used, not by ``slots × max_seq`` padding.
- :class:`PageAllocator` / :class:`PageLease` — the host-side authority over
  the page pool: refcounted alloc/share/release plus the copy-on-write fault
  path, so identical prefixes can resolve to the *same* physical pages
  (launch/prefix_cache.py builds the radix prefix index on top of it).

Per-layer entry layouts (unchanged from the dict era — entries stay plain
dicts because they are heterogeneous by block kind):

- ``full`` attention layers: k/v of shape (batch, kv_heads, max_seq, head_dim);
  valid entries are positions [0, pos).
- ``swa`` layers: ring buffer of length ``window`` — slot = position % window,
  plus a per-slot ``slot_pos`` array so masking survives wrap-around.
- ``rec`` layers (RG-LRU): hidden state (batch, width) + conv tail.
- ``ssd`` layers (Mamba-2): state (batch, nheads, head_dim, d_state) + conv.

Entries are stacked along a leading cycle axis to match the scan-over-layers
execution (see transformer.py).
"""
from __future__ import annotations

import dataclasses
import hashlib
import warnings
from dataclasses import dataclass
from functools import partial
from typing import Callable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

# Additive attention-logit bias that masks an absent/inactive fused-prefix key.
# exp(PREFIX_MASK_BIAS - m) underflows to exactly 0 in fp32 softmax, so a fully
# masked prefix is *identical* to decoding with no prefix at all — the property
# that lets launch/engine.py keep one fixed-shape fused bucket per slot.
PREFIX_MASK_BIAS = -1e30


def pytree_dataclass(data_fields: Sequence[str], meta_fields: Sequence[str] = ()):
    """Register a dataclass as a jax pytree (data vs. static fields)."""
    return partial(jax.tree_util.register_dataclass,
                   data_fields=list(data_fields),
                   meta_fields=list(meta_fields))


def tree_bytes(tree) -> int:
    """Total bytes of every array leaf in ``tree`` (HBM/wire accounting)."""
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree.leaves(tree)
               if hasattr(leaf, "dtype"))


# ------------------------------------------------------- per-layer builders


def init_attn_kv(
    cycles: int, batch: int, kv_heads: int, max_seq: int, head_dim: int, dtype
) -> dict:
    return {
        "k": jnp.zeros((cycles, batch, kv_heads, max_seq, head_dim), dtype),
        "v": jnp.zeros((cycles, batch, kv_heads, max_seq, head_dim), dtype),
    }


def init_swa_kv(
    cycles: int, batch: int, kv_heads: int, window: int, head_dim: int, dtype
) -> dict:
    return {
        "k": jnp.zeros((cycles, batch, kv_heads, window, head_dim), dtype),
        "v": jnp.zeros((cycles, batch, kv_heads, window, head_dim), dtype),
        # absolute position held by each ring slot; -1 = empty
        "slot_pos": jnp.full((cycles, batch, window), -1, jnp.int32),
    }


def init_rec_state(cycles: int, batch: int, width: int, conv_k: int, dtype) -> dict:
    return {
        "h": jnp.zeros((cycles, batch, width), jnp.float32),  # recurrence fp32
        "conv": jnp.zeros((cycles, batch, conv_k - 1, width), dtype),
    }


def init_ssd_state(
    cycles: int, batch: int, nheads: int, head_dim: int, d_state: int,
    conv_dim: int, conv_k: int, dtype
) -> dict:
    return {
        "h": jnp.zeros((cycles, batch, nheads, head_dim, d_state), jnp.float32),
        "conv": jnp.zeros((cycles, batch, conv_k - 1, conv_dim), dtype),
    }


def _grouping(cfg: ModelConfig):
    from repro.models.transformer import layer_grouping

    return layer_grouping(cfg)


# ----------------------------------------------------------------- KVStack


@pytree_dataclass(["k", "v"])
@dataclass
class KVStack:
    """The communicated KV tensor: k/v of shape (n_attn, B, Hkv, S, hd).

    This is what C2C ships over the wire: the transmitter exports it
    (:meth:`KVCache.export_stack`), a channel encodes it (core/transport.py),
    the fuser projects it, the receiver prepends it.
    """

    k: jax.Array
    v: jax.Array

    def __getitem__(self, key: str) -> jax.Array:
        warnings.warn(
            "KVStack[...] dict-style access is deprecated; use attribute "
            "access (stack.k / stack.v)", DeprecationWarning, stacklevel=2)
        return getattr(self, key)

    @property
    def seq_len(self) -> int:
        return self.k.shape[-2]

    @property
    def nbytes(self) -> int:
        return tree_bytes(self)

    def astype(self, dtype) -> "KVStack":
        return KVStack(self.k.astype(dtype), self.v.astype(dtype))

    def slice_length(self, length: int) -> "KVStack":
        return KVStack(self.k[..., :length, :], self.v[..., :length, :])

    def prepend(self, fused: "KVStack") -> "KVStack":
        """Sequence-wise concatenation ``C(F_ij, M_i) ∘ C(M_j)`` of Eq. 1/4:
        the fused (projected transmitter) stack is *prepended*, matching the
        paper's decode equation where the receiver's own running cache stays
        contiguous at the tail."""
        return KVStack(
            k=jnp.concatenate([fused.k, self.k], axis=-2),
            v=jnp.concatenate([fused.v, self.v], axis=-2),
        )

    @classmethod
    def ensure(cls, obj) -> "KVStack":
        if isinstance(obj, cls):
            return obj
        if isinstance(obj, dict):
            return cls(k=obj["k"], v=obj["v"])
        return cls(k=obj.k, v=obj.v)  # e.g. FusedPrefix (drops bias)


# -------------------------------------------------------------- FusedPrefix


@pytree_dataclass(["k", "v", "bias"])
@dataclass
class FusedPrefix:
    """A receiver-space fused prefix: k/v (n_rx, B, Hkv, Sf, hd) plus a
    per-layer, per-position attention-logit ``bias`` (n_rx, B, Sf) fp32.

    The bias carries the fuser/gating attention-mass gates AND the padding
    mask: a position with bias :data:`PREFIX_MASK_BIAS` contributes exactly
    zero attention mass, which is what keeps the engine's fixed-bucket decode
    step exact for any request mix."""

    k: jax.Array
    v: jax.Array
    bias: Optional[jax.Array] = None

    def __getitem__(self, key: str) -> jax.Array:
        warnings.warn(
            "FusedPrefix[...] dict-style access is deprecated; use attribute "
            "access (fused.k / fused.v / fused.bias)",
            DeprecationWarning, stacklevel=2)
        return getattr(self, key)

    @property
    def seq_len(self) -> int:
        return self.k.shape[-2]

    @property
    def nbytes(self) -> int:
        return tree_bytes(self)

    def with_bias(self, bias: jax.Array) -> "FusedPrefix":
        return dataclasses.replace(self, bias=bias)

    def _bias_or_zero(self) -> jax.Array:
        if self.bias is not None:
            return self.bias.astype(jnp.float32)
        n, B, _, S, _ = self.k.shape
        return jnp.zeros((n, B, S), jnp.float32)

    @classmethod
    def ensure(cls, obj) -> "FusedPrefix":
        if isinstance(obj, cls):
            return obj
        if isinstance(obj, dict):
            return cls(k=obj["k"], v=obj["v"], bias=obj.get("bias"))
        return cls(k=obj.k, v=obj.v, bias=getattr(obj, "bias", None))

    # ----------------------------------------------------------- builders
    @classmethod
    def empty(cls, cfg: ModelConfig, batch: int, max_prefix: int,
              dtype=jnp.float32) -> "FusedPrefix":
        """All-masked prefix: k/v zeros and bias PREFIX_MASK_BIAS everywhere.
        Decoding against it equals standalone decoding exactly."""
        n = len(cfg.attention_layers)
        hd = cfg.resolved_head_dim
        shape = (n, batch, cfg.num_kv_heads, max_prefix, hd)
        return cls(
            k=jnp.zeros(shape, dtype),
            v=jnp.zeros(shape, dtype),
            bias=jnp.full((n, batch, max_prefix), PREFIX_MASK_BIAS, jnp.float32),
        )

    @classmethod
    def concat(cls, prefixes: Sequence["FusedPrefix"]) -> "FusedPrefix":
        """Eq. 4's sequence-wise concatenation C(F_{j1 i}) ∘ … ∘ C(F_{js i})."""
        ps = [cls.ensure(p) for p in prefixes]
        return cls(
            k=jnp.concatenate([p.k for p in ps], axis=-2),
            v=jnp.concatenate([p.v for p in ps], axis=-2),
            bias=jnp.concatenate([p._bias_or_zero() for p in ps], axis=-1),
        )

    # --------------------------------------------------------- transforms
    def pad(self, max_prefix: int) -> "FusedPrefix":
        """Right-pad to the fixed ``max_prefix`` bucket; padded positions get
        bias PREFIX_MASK_BIAS (zero attention mass). This is what keeps the
        engine's decode step shape-stable across request mixes."""
        n, B, H, S, hd = self.k.shape
        if S > max_prefix:
            raise ValueError(
                f"fused prefix length {S} exceeds max_prefix {max_prefix}")
        pad = max_prefix - S
        return FusedPrefix(
            k=jnp.pad(self.k, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0))),
            v=jnp.pad(self.v, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0))),
            bias=jnp.pad(self._bias_or_zero(), ((0, 0), (0, 0), (0, pad)),
                         constant_values=PREFIX_MASK_BIAS),
        )

    def insert_slot(self, slot, req: "FusedPrefix") -> "FusedPrefix":
        """Scatter a single request's padded prefix (n, 1, Hkv, P, hd) into
        batch index ``slot`` of a per-slot fused table."""
        slot = jnp.asarray(slot, jnp.int32)
        z = jnp.zeros((), jnp.int32)
        req = FusedPrefix.ensure(req)
        return FusedPrefix(
            k=jax.lax.dynamic_update_slice(
                self.k, req.k.astype(self.k.dtype), (z, slot, z, z, z)),
            v=jax.lax.dynamic_update_slice(
                self.v, req.v.astype(self.v.dtype), (z, slot, z, z, z)),
            bias=jax.lax.dynamic_update_slice(
                self._bias_or_zero(), req._bias_or_zero(), (z, slot, z)),
        )

    def to_extra_kv(self, cfg: ModelConfig) -> list:
        """Slice into the per-position ``extra_kv`` list that
        transformer.forward / decode_step consume (one stacked
        :class:`FusedPrefix` entry per pattern position, then tail positions;
        non-attention positions None)."""
        cycles, pattern, tail = _grouping(cfg)
        bias = self.bias
        out: List[Optional[FusedPrefix]] = []
        off = 0

        def slice_at(o, n):
            return FusedPrefix(
                k=self.k[o: o + n], v=self.v[o: o + n],
                bias=None if bias is None else bias[o: o + n])

        for kind in pattern:
            if kind in ("attn", "swa"):
                out.append(slice_at(off, cycles))
                off += cycles
            else:
                out.append(None)
        for kind in tail:
            if kind in ("attn", "swa"):
                out.append(slice_at(off, 1))
                off += 1
            else:
                out.append(None)
        return out


def extra_kv_layers(cfg: ModelConfig, fused) -> list:
    """Back-compat shim: ``FusedPrefix.ensure(fused).to_extra_kv(cfg)``."""
    return FusedPrefix.ensure(fused).to_extra_kv(cfg)


def fused_digest(fused) -> str:
    """Content digest of a fused prefix (sha1 over shapes, dtypes and bytes).

    This is the identity under which a C2C prefix is shared: the engine keys
    its fused-row table and the radix prefix index on it, so a prefix a peer
    transmitted *once* is inserted once and every later request fusing the
    same digest reuses that row — and prompt pages are only shared between
    requests that attended the same fused prefix during prefill."""
    with jax.profiler.TraceAnnotation("fr.digest"):
        f = FusedPrefix.ensure(fused)
        # the copy to the host, which first waits for the device to make it
        with jax.profiler.TraceAnnotation("fr.fetch"):
            arrs = [np.asarray(leaf)
                    for leaf in (f.k, f.v, f._bias_or_zero())]
        with jax.profiler.TraceAnnotation("fr.hash"):
            h = hashlib.sha1()
            for arr in arrs:
                h.update(str(arr.shape).encode())
                h.update(str(arr.dtype).encode())
                h.update(arr.tobytes())
            return h.hexdigest()


# ------------------------------------------------------------------ KVCache


def _insert_slot_leaf(table_leaf: jax.Array, req_leaf: jax.Array,
                      slot: jax.Array, batch_index: jax.Array) -> jax.Array:
    # every cache leaf is (cycles, batch, ...): scatter the request's block at
    # batch index ``batch_index`` of ``req_leaf`` into row ``slot``
    blk = jax.lax.dynamic_slice_in_dim(req_leaf, batch_index, 1, axis=1)
    start = (jnp.zeros((), jnp.int32), slot) + tuple(
        jnp.zeros((), jnp.int32) for _ in range(table_leaf.ndim - 2))
    return jax.lax.dynamic_update_slice(
        table_leaf, blk.astype(table_leaf.dtype), start)


@pytree_dataclass(["pos", "layers"])
@dataclass
class KVCache:
    """A model's decode state: ``pos`` (scalar, or per-slot (B,) vector for
    continuous batching) + per-pattern-position stacked layer entries."""

    pos: jax.Array
    layers: Tuple

    def __getitem__(self, key: str):
        warnings.warn(
            "KVCache[...] dict-style access is deprecated; use attribute "
            "access (cache.pos / cache.layers)",
            DeprecationWarning, stacklevel=2)
        return getattr(self, key)

    @property
    def nbytes(self) -> int:
        return tree_bytes(self)

    def with_pos(self, pos) -> "KVCache":
        return KVCache(pos=jnp.asarray(pos, jnp.int32), layers=self.layers)

    @classmethod
    def ensure(cls, obj) -> "KVCache":
        if isinstance(obj, cls):
            return obj
        if isinstance(obj, dict):
            return cls(pos=obj["pos"], layers=tuple(obj["layers"]))
        return cls(pos=obj.pos, layers=tuple(obj.layers))

    # ----------------------------------------------------------- builders
    @classmethod
    def init(
        cls,
        cfg: ModelConfig,
        batch: int,
        max_seq: int,
        dtype=jnp.bfloat16,
        *,
        window_override: Optional[int] = None,
    ) -> "KVCache":
        """Build the full decode cache for ``cfg`` (transformer.py grouping)."""
        cycles, pattern, tail = _grouping(cfg)
        hd = cfg.resolved_head_dim
        layers = []
        for pos, kind in enumerate(pattern + tail):
            n = cycles if pos < len(pattern) else 1
            if kind == "attn":
                layers.append(
                    init_attn_kv(n, batch, cfg.num_kv_heads, max_seq, hd, dtype))
            elif kind == "swa":
                w = min(window_override or cfg.sliding_window
                        or cfg.long_context_window, max_seq)
                layers.append(
                    init_swa_kv(n, batch, cfg.num_kv_heads, w, hd, dtype))
            elif kind == "rec":
                width = cfg.rglru_width or cfg.d_model
                layers.append(
                    init_rec_state(n, batch, width, cfg.conv_kernel, dtype))
            elif kind == "ssd":
                conv_dim = cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state
                layers.append(
                    init_ssd_state(n, batch, cfg.ssm_nheads, cfg.ssm_head_dim,
                                   cfg.ssm_state, conv_dim, cfg.conv_kernel,
                                   dtype))
            else:
                raise ValueError(f"unknown layer kind {kind!r}")
        return cls(pos=jnp.zeros((), jnp.int32), layers=tuple(layers))

    @classmethod
    def init_slots(
        cls,
        cfg: ModelConfig,
        slots: int,
        max_seq: int,
        dtype=jnp.bfloat16,
        *,
        window_override: Optional[int] = None,
    ) -> "KVCache":
        """A decode cache whose batch axis is a *dense slot table*: ``pos`` is
        per-slot (slots,) int32 so every slot decodes at its own position
        (continuous batching — launch/engine.py; the paged alternative is
        :class:`SlotTable`). Consumed by transformer.decode_step's
        vector-``pos`` path."""
        c = cls.init(cfg, slots, max_seq, dtype, window_override=window_override)
        return c.with_pos(jnp.zeros((slots,), jnp.int32))

    # ------------------------------------------------------------- export
    def export_stack(self, cfg: ModelConfig,
                     length: Optional[int] = None) -> KVStack:
        """Collect all attention-layer k/v into one (n_attn, B, Hkv, S, hd)
        stack — the tensor C2C communicates. Pattern positions + tail are
        concatenated in layer order along the leading axis."""
        cycles, pattern, tail = _grouping(cfg)
        ks, vs = [], []
        for i, kind in enumerate(pattern + tail):
            if kind in ("attn", "swa"):
                e = self.layers[i]
                ks.append(e["k"])
                vs.append(e["v"])
        stack = KVStack(k=jnp.concatenate(ks, axis=0),
                        v=jnp.concatenate(vs, axis=0))
        if length is not None:
            stack = stack.slice_length(length)
        return stack

    # ------------------------------------------------- dense slot lifecycle
    def insert_slot(self, slot, req: "KVCache", length, lease=None, *,
                    batch_index=0) -> "KVCache":
        """Insert one request of a (possibly batched) prefill cache into slot
        ``slot`` and set that slot's position to ``length``.

        ``lease`` is accepted (and ignored) so engine call sites are
        polymorphic over paged vs dense: :meth:`SlotTable.insert_slot` takes an
        allocator-issued :class:`PageLease` in the same positional slot.

        Stale K/V beyond ``length`` (from a previous occupant) never need
        zeroing: the per-slot position mask hides them, and decode overwrites
        each index before it first becomes visible."""
        del lease  # dense slots own a full row; nothing to map
        slot = jnp.asarray(slot, jnp.int32)
        bi = jnp.asarray(batch_index, jnp.int32)
        req = KVCache.ensure(req)
        layers = tuple(
            jax.tree.map(lambda t, r: _insert_slot_leaf(t, r, slot, bi), tl, rl)
            for tl, rl in zip(self.layers, req.layers)
        )
        pos = self.pos.at[slot].set(jnp.asarray(length, jnp.int32))
        return KVCache(pos=pos, layers=layers)

    def evict_slot(self, slot) -> "KVCache":
        """Free a slot immediately: reset its position (stale K/V stay but are
        masked — see insert_slot)."""
        return self.with_pos(
            self.pos.at[jnp.asarray(slot, jnp.int32)].set(0))


# ---------------------------------------------------------------- SlotTable


@pytree_dataclass(["pos", "page_map", "layers"], ["page_size"])
@dataclass
class SlotTable:
    """Paged engine slot table: block/paged KV layout.

    Instead of a dense (slots, max_seq) row per slot, attention K/V live in a
    shared *page pool* of fixed-size pages — per layer entry,
    k/v: (n, num_pages, Hkv, page_size, hd) — and each slot owns an ordered
    ``page_map`` row (slots, pages_per_slot) of physical page ids. A slot's
    HBM cost is the pages it actually needs (ceil(tokens/page_size)), so at a
    fixed pool budget the table sustains far more concurrent slots than the
    dense layout whenever requests are shorter than ``max_seq``.

    ``INVALID_PAGE`` (== num_pages, an out-of-bounds id) marks unallocated
    map entries: scatters through it are dropped and gathers clamp to an
    arbitrary page whose content is hidden by the per-slot position mask —
    exactly the mask that already hides a dense slot's stale K/V, so paged
    decode is *byte-identical* to dense decode (engine_bench verifies).

    Page allocation/free is host-side policy owned by :class:`PageAllocator`
    (refcounts, sharing, CoW); this class only does the device-side
    scatter/gather, including the CoW fault's :meth:`copy_page` and the
    prefix-cache :meth:`prefix_extra_kv`/:meth:`insert_suffix` pair.
    """

    pos: jax.Array  # (slots,) int32
    page_map: jax.Array  # (slots, pages_per_slot) int32 physical page ids
    layers: Tuple  # per position: {"k","v"} pools (n, num_pages, Hkv, pg, hd)
    page_size: int

    @property
    def num_slots(self) -> int:
        return self.pos.shape[0]

    @property
    def pages_per_slot(self) -> int:
        return self.page_map.shape[1]

    @property
    def num_pages(self) -> int:
        return self.layers[0]["k"].shape[1]

    @property
    def view_seq(self) -> int:
        """Per-slot logical sequence length of the gathered dense view."""
        return self.pages_per_slot * self.page_size

    @property
    def invalid_page(self) -> int:
        return self.num_pages

    @property
    def nbytes(self) -> int:
        return tree_bytes(self)

    def with_pos(self, pos) -> "SlotTable":
        return dataclasses.replace(self, pos=jnp.asarray(pos, jnp.int32))

    @classmethod
    def init(
        cls,
        cfg: ModelConfig,
        slots: int,
        max_seq: int,
        dtype=jnp.bfloat16,
        *,
        page_size: int = 16,
        num_pages: Optional[int] = None,
    ) -> "SlotTable":
        """Pool-backed slot table. Requires a pure full-attention model (ring
        buffers and recurrent state have O(1)-per-slot cost and no paging
        upside; they keep the dense layout)."""
        if any(k != "attn" for k in cfg.block_pattern):
            raise ValueError(
                f"paged SlotTable requires a pure full-attention pattern; "
                f"{cfg.name} has {cfg.block_pattern}")
        if max_seq % page_size:
            raise ValueError(f"max_seq={max_seq} not divisible by "
                             f"page_size={page_size}")
        pages_per_slot = max_seq // page_size
        num_pages = num_pages if num_pages is not None else slots * pages_per_slot
        cycles, pattern, tail = _grouping(cfg)
        hd = cfg.resolved_head_dim
        layers = []
        for pos, _ in enumerate(pattern + tail):
            n = cycles if pos < len(pattern) else 1
            shape = (n, num_pages, cfg.num_kv_heads, page_size, hd)
            layers.append({"k": jnp.zeros(shape, dtype),
                           "v": jnp.zeros(shape, dtype)})
        return cls(
            pos=jnp.zeros((slots,), jnp.int32),
            page_map=jnp.full((slots, pages_per_slot), num_pages, jnp.int32),
            layers=tuple(layers),
            page_size=page_size,
        )

    # ----------------------------------------------------- paged attention
    @staticmethod
    def write_token(pool: jax.Array, tok: jax.Array, page_map: jax.Array,
                    pos: jax.Array, page_size: int) -> jax.Array:
        """Scatter one new K (or V) token per slot straight into its physical
        page — the in-place write the paged decode path uses instead of
        writing into a gathered view and committing back.

        ``pool`` (num_pages, Hkv, page_size, hd); ``tok`` (slots, Hkv, hd);
        ``pos`` (slots,) absolute write position. Slots whose covering page
        map entry is INVALID_PAGE (inactive/evicted) are dropped by the
        scatter, so they can never corrupt pages reassigned to others."""
        pps = page_map.shape[1]
        page_idx = jnp.clip(pos // page_size, 0, pps - 1)
        phys = jnp.take_along_axis(page_map, page_idx[:, None], axis=1)[:, 0]
        off = pos % page_size
        return pool.at[phys, :, off].set(tok.astype(pool.dtype), mode="drop")

    @staticmethod
    def attend(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
               page_map: jax.Array, lengths: jax.Array):
        """GQA flash-decode over the page pool *in place* — the hot-loop path
        that replaces ``dense_view()`` gathering. kernels/paged_attention.py
        runs one grid step per slot, all kv heads at once, and loops over
        only the slot's live blocks of P page-map entries (P from the page's
        byte size), copying each mapped page inside the length from the pool
        and skipping INVALID ones.

        q (slots, H, hd); pools (num_pages, Hkv, page_size, hd); lengths
        (slots,) live tokens. Returns (out (slots, H, hd), m, l) online
        softmax stats so callers can LSE-merge a fused C2C prefix segment."""
        from repro.kernels import ops

        return ops.paged_decode_attention(q, k_pool, v_pool, page_map, lengths)

    # ------------------------------------------------------------- views
    def dense_view(self) -> KVCache:
        """Gather each slot's pages into a contiguous per-slot cache
        (n, slots, Hkv, view_seq, hd) — the layout transformer.decode_step's
        dense path consumes. Unallocated pages clamp to an arbitrary pool
        page; the per-slot position mask hides their content (exact-zero
        attention mass), so the view decodes byte-identically to a dense
        table. The decode hot loop now attends in place (:meth:`attend`);
        this gather survives for export, debugging and parity checks."""
        pm = jnp.minimum(self.page_map, self.num_pages - 1)  # clamp sentinel
        slots, pps = pm.shape

        def gather(pool):
            n, _, H, pg, hd = pool.shape
            v = pool[:, pm]  # (n, slots, pps, Hkv, pg, hd)
            v = v.transpose(0, 1, 3, 2, 4, 5)
            return v.reshape(n, slots, H, pps * pg, hd)

        layers = tuple({"k": gather(e["k"]), "v": gather(e["v"])}
                       for e in self.layers)
        return KVCache(pos=self.pos, layers=layers)

    # --------------------------------------------------------- lifecycle
    def insert_slot(self, slot, req: KVCache, length, lease,
                    *, batch_index=0) -> "SlotTable":
        """Insert one request of a prefill cache (row layout, seq length ==
        ``view_seq``) into slot ``slot``: scatter its pages into the pool at
        the leased page ids and point the slot's page map at them.

        ``lease`` is an allocator-issued :class:`PageLease` — or, for jitted
        call sites, its pre-built page row ((pages_per_slot,) int32,
        INVALID_PAGE-padded beyond the allocated count). Same positional slot
        as :meth:`KVCache.insert_slot`'s ignored ``lease``."""
        slot = jnp.asarray(slot, jnp.int32)
        bi = jnp.asarray(batch_index, jnp.int32)
        if isinstance(lease, PageLease):
            lease = lease.page_row(self.pages_per_slot, self.invalid_page)
        page_ids = jnp.asarray(lease, jnp.int32)
        req = KVCache.ensure(req)
        pps, pg = self.pages_per_slot, self.page_size

        def scatter(pool, row):
            # row: (n, B, Hkv, view_seq, hd) -> request bi's pages
            n, _, H, S, hd = row.shape
            blk = jax.lax.dynamic_slice_in_dim(row, bi, 1, axis=1)[:, 0]
            pages = blk.reshape(n, H, pps, pg, hd).transpose(0, 2, 1, 3, 4)
            # scatter (n, pps, Hkv, pg, hd) at pool axis 1; INVALID ids drop
            return pool.at[:, page_ids].set(pages.astype(pool.dtype),
                                            mode="drop")

        layers = tuple(
            {"k": scatter(e["k"], r["k"]), "v": scatter(e["v"], r["v"])}
            for e, r in zip(self.layers, req.layers)
        )
        return SlotTable(
            pos=self.pos.at[slot].set(jnp.asarray(length, jnp.int32)),
            page_map=self.page_map.at[slot].set(page_ids),
            layers=layers,
            page_size=self.page_size,
        )

    def insert_suffix(self, slot, req: KVCache, phys, off, lease_row,
                      length) -> "SlotTable":
        """Insert a *suffix* prefill: the prompt's first ``P`` tokens were
        served from shared pages (radix prefix-cache hit), so ``req`` holds
        K/V only for positions [P, S) in rows [0, S-P). Scatter token ``i``
        to pool page ``phys[i]`` at in-page offset ``off[i]`` (INVALID ids
        drop — padded rows), adopt the slot's full page row (shared prefix
        pages + freshly written suffix pages) and set its position to
        ``length`` (= S). CoW happened before this call: any shared page the
        suffix writes into was already copied (:meth:`copy_page`), so
        ``phys`` only ever targets pages this slot owns."""
        slot = jnp.asarray(slot, jnp.int32)
        phys = jnp.asarray(phys, jnp.int32)
        off = jnp.asarray(off, jnp.int32)
        if isinstance(lease_row, PageLease):
            lease_row = lease_row.page_row(self.pages_per_slot,
                                           self.invalid_page)
        lease_row = jnp.asarray(lease_row, jnp.int32)
        req = KVCache.ensure(req)

        def scatter(pool, row):
            # row (n, 1, Hkv, Ssuf, hd) -> per-token (Ssuf, n, Hkv, hd), the
            # shape advanced indexing wants for pool.at[:, phys, :, off]
            tok = row[:, 0].transpose(2, 0, 1, 3)
            return pool.at[:, phys, :, off].set(tok.astype(pool.dtype),
                                                mode="drop")

        layers = tuple(
            {"k": scatter(e["k"], r["k"]), "v": scatter(e["v"], r["v"])}
            for e, r in zip(self.layers, req.layers)
        )
        return SlotTable(
            pos=self.pos.at[slot].set(jnp.asarray(length, jnp.int32)),
            page_map=self.page_map.at[slot].set(lease_row),
            layers=layers,
            page_size=self.page_size,
        )

    def adopt_slot(self, slot, page_row, length) -> "SlotTable":
        """Activate a slot whose pages were already filled out-of-band.

        Chunked prefill (transformer.prefill_chunk) scatters K/V through
        per-token phys/off while the slot's page-map row stays INVALID — so
        decode's :meth:`write_token` cannot touch the in-flight pages and the
        slot is invisible to the batch. On the prompt's final chunk the engine
        adopts the lease's row and sets the live length; the next decode step
        sees a fully prefilled slot. No pool data moves."""
        slot = jnp.asarray(slot, jnp.int32)
        if isinstance(page_row, PageLease):
            page_row = page_row.page_row(self.pages_per_slot,
                                         self.invalid_page)
        return dataclasses.replace(
            self,
            pos=self.pos.at[slot].set(jnp.asarray(length, jnp.int32)),
            page_map=self.page_map.at[slot].set(
                jnp.asarray(page_row, jnp.int32)),
        )

    def copy_page(self, src, dst) -> "SlotTable":
        """Copy one physical page's K/V (every layer entry) ``src`` → ``dst``:
        the device half of the allocator's copy-on-write fault. The host side
        (:meth:`PageAllocator.cow`) re-points the faulting slot's lease at
        ``dst`` so the write that triggered the fault lands in the copy."""
        src = jnp.asarray(src, jnp.int32)
        dst = jnp.asarray(dst, jnp.int32)

        def cp(pool):
            page = jax.lax.dynamic_slice_in_dim(pool, src, 1, axis=1)
            return jax.lax.dynamic_update_slice_in_dim(pool, page, dst, axis=1)

        layers = tuple({"k": cp(e["k"]), "v": cp(e["v"])}
                       for e in self.layers)
        return dataclasses.replace(self, layers=layers)

    def prefix_extra_kv(self, page_ids, length) -> list:
        """Gather already-cached prefix pages into the per-position
        ``extra_kv`` list transformer.prefill consumes, so a radix-hit
        admission prefills only the suffix while attending the cached prefix.

        ``page_ids`` ((n_prefix_pages,) int32, INVALID-padded — fixed length
        keeps one trace) select pool pages; positions ≥ ``length`` (a traced
        scalar: the matched-prefix token count) get bias PREFIX_MASK_BIAS, so
        padding and the stale tail of a partially-matched page contribute
        exactly zero attention mass."""
        page_ids = jnp.asarray(page_ids, jnp.int32)
        pm = jnp.minimum(page_ids, self.num_pages - 1)  # clamp sentinel
        npp = page_ids.shape[0]
        pg = self.page_size
        mask = jnp.where(jnp.arange(npp * pg)[None, None, :]
                         < jnp.asarray(length, jnp.int32),
                         0.0, PREFIX_MASK_BIAS).astype(jnp.float32)

        def gather(pool):
            n, _, H, _, hd = pool.shape
            v = pool[:, pm]  # (n, npp, Hkv, pg, hd)
            v = v.transpose(0, 2, 1, 3, 4).reshape(n, H, npp * pg, hd)
            return v[:, None]  # (n, 1, Hkv, npp*pg, hd)

        out = []
        for e in self.layers:
            k = gather(e["k"])
            out.append(FusedPrefix(
                k=k, v=gather(e["v"]),
                bias=jnp.broadcast_to(mask, (k.shape[0], 1, npp * pg))))
        return out

    def evict_slot(self, slot) -> "SlotTable":
        """Free a slot: reset its position and unmap its pages. (Returning the
        physical pages to the free pool is the host-side allocator's job.)"""
        slot = jnp.asarray(slot, jnp.int32)
        return SlotTable(
            pos=self.pos.at[slot].set(0),
            page_map=self.page_map.at[slot].set(self.invalid_page),
            layers=self.layers,
            page_size=self.page_size,
        )

    def commit(self, new_view: KVCache, pos_out: jax.Array) -> "SlotTable":
        """Fold one decode step back into the pool: decode_step wrote exactly
        one token per slot (at the slot's pre-step position) into the gathered
        dense view; scatter those tokens to their physical pages and adopt
        ``pos_out`` (the engine's activity-masked position vector). Slots
        whose page map entry is INVALID_PAGE (inactive/evicted) are dropped by
        the scatter, so they can never corrupt pages reassigned to others."""
        old_pos = self.pos  # position each slot's new token was written at
        slots = self.num_slots
        page_idx = jnp.clip(old_pos // self.page_size, 0,
                            self.pages_per_slot - 1)
        phys = jnp.take_along_axis(self.page_map, page_idx[:, None],
                                   axis=1)[:, 0]  # (slots,)
        off = old_pos % self.page_size
        rows = jnp.arange(slots)

        def scatter(pool, view):
            # token written this step: view[(n, slots, Hkv, view_seq, hd)] at
            # [:, s, :, old_pos[s], :] -> (slots, n, Hkv, hd) (adv-idx moves
            # the indexed axes to the front)
            tok = view[:, rows, :, old_pos, :]
            return pool.at[:, phys, :, off].set(tok.astype(pool.dtype),
                                                mode="drop")

        layers = tuple(
            {"k": scatter(e["k"], ve["k"]), "v": scatter(e["v"], ve["v"])}
            for e, ve in zip(self.layers, new_view.layers)
        )
        return SlotTable(pos=pos_out, page_map=self.page_map, layers=layers,
                         page_size=self.page_size)


# ------------------------------------------------------------ PageAllocator


@dataclass
class PageLease:
    """An allocator-issued grant of physical pages to one slot, in slot order.

    ``owned[i]`` marks exclusivity: the slot may write into page
    ``page_ids[i]`` only when True. Shared (``owned`` False) pages are
    read-only for this slot — a write there must go through the allocator's
    CoW fault (:meth:`PageAllocator.cow`) first, which re-points the lease at
    a private copy. Leases are host-side handles (numpy), never traced;
    :meth:`page_row` builds the INVALID-padded device row jitted call sites
    take."""

    page_ids: np.ndarray  # (n,) int32 physical page ids, slot order
    owned: np.ndarray     # (n,) bool, True = exclusive/writable

    @property
    def num_pages(self) -> int:
        return int(self.page_ids.size)

    def ids(self) -> List[int]:
        return [int(p) for p in self.page_ids]

    def shared_ids(self) -> List[int]:
        return [int(p) for p, o in zip(self.page_ids, self.owned) if not o]

    def page_row(self, pages_per_slot: int, invalid: int) -> np.ndarray:
        """The slot's (pages_per_slot,) page-map row, INVALID-padded."""
        if self.num_pages > pages_per_slot:
            raise ValueError(f"lease of {self.num_pages} pages exceeds "
                             f"pages_per_slot={pages_per_slot}")
        row = np.full(pages_per_slot, invalid, np.int32)
        row[: self.num_pages] = self.page_ids
        return row


class PageAllocator:
    """Host-side refcounted authority over a :class:`SlotTable`'s page pool —
    the *only* way pages are granted, shared or returned (the engine holds
    :class:`PageLease` handles, never raw page-id lists).

    A page's refcount counts every holder: each slot lease mapping it plus
    each prefix-index pin (:meth:`retain`). ``alloc`` grants exclusive pages
    at refcount 1; ``share`` increfs pages another holder already owns;
    ``release`` decrefs and returns a page to the free list exactly when its
    count reaches zero — so evicting one sharer can never free pages another
    slot still maps. ``cow`` is the copy-on-write fault path: the faulting
    lease swaps its share of a page for a fresh exclusive one (the caller
    performs the device copy via :meth:`SlotTable.copy_page`).

    Double-free and free-page sharing raise instead of corrupting state;
    :meth:`assert_consistent` is the property-test hook."""

    def __init__(self, num_pages: int):
        if num_pages < 0:
            raise ValueError("num_pages must be >= 0")
        self.num_pages = num_pages
        self._refcounts = np.zeros(num_pages, np.int64)
        self._free: List[int] = list(range(num_pages))
        # optional () -> str callback naming the current holders (per-slot
        # page counts, pinned digests, sanitizer provenance); its output is
        # appended to pool-exhaustion errors so they are actionable
        self.holders_hook: Optional[Callable[[], str]] = None

    def _exhausted(self, requested: int, what: str) -> RuntimeError:
        msg = (f"page pool exhausted: requested {requested} {what}, "
               f"free {len(self._free)} of {self.num_pages} "
               f"({self.pages_in_use} in use)")
        if self.holders_hook is not None:
            detail = self.holders_hook()
            if detail:
                msg += "\ncurrent holders:\n" + detail
        return RuntimeError(msg)

    # ------------------------------------------------------------ queries
    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self.num_pages - len(self._free)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def refcount(self, page_id: int) -> int:
        return int(self._refcounts[page_id])

    # ---------------------------------------------------------- lifecycle
    def alloc(self, n: int) -> List[int]:
        """Grant ``n`` exclusive pages (refcount 1 each)."""
        if n > len(self._free):
            raise self._exhausted(n, "pages")
        ids = [self._free.pop() for _ in range(n)]
        self._refcounts[ids] += 1
        return ids

    def share(self, page_ids: Sequence[int]) -> List[int]:
        """Add a reference to pages some other holder already owns."""
        ids = [int(p) for p in page_ids]
        for p in ids:
            if self._refcounts[p] <= 0:
                raise ValueError(f"cannot share free page {p}")
        self._refcounts[ids] += 1
        return ids

    def retain(self, page_id: int) -> None:
        """Pin a single live page (prefix-index references use this)."""
        self.share([page_id])

    def release(self, pages: Union["PageLease", Sequence[int]]) -> None:
        """Drop one reference per page; free pages whose count hits zero."""
        ids = pages.ids() if isinstance(pages, PageLease) else \
            [int(p) for p in pages]
        for p in ids:
            if self._refcounts[p] <= 0:
                raise ValueError(f"refcount underflow: page {p} already free")
            self._refcounts[p] -= 1
            if self._refcounts[p] == 0:
                self._free.append(p)

    def lease(self, *, shared: Sequence[int] = (), fresh: int = 0) -> PageLease:
        """Issue a slot's lease: incref ``shared`` prefix pages (in order)
        followed by ``fresh`` newly-allocated exclusive pages."""
        if fresh > len(self._free):
            raise self._exhausted(fresh, "fresh pages")
        s = self.share(shared)
        f = self.alloc(fresh)
        return PageLease(
            page_ids=np.asarray(s + f, np.int32),
            owned=np.asarray([False] * len(s) + [True] * fresh, bool),
        )

    def cow(self, lease: PageLease, index: int) -> Tuple[int, int]:
        """Copy-on-write fault: the slot is about to write into shared page
        ``lease.page_ids[index]``. Allocate a private copy target, swap it
        into the lease (now owned) and drop the share of the source. Returns
        ``(src, dst)`` — the caller must copy the page's bytes on device
        (:meth:`SlotTable.copy_page`) before writing."""
        if lease.owned[index]:
            raise ValueError(f"page at lease index {index} is already owned; "
                             f"CoW fault is only valid on shared pages")
        src = int(lease.page_ids[index])
        dst = self.alloc(1)[0]
        self.release([src])
        lease.page_ids[index] = dst
        lease.owned[index] = True
        return src, dst

    # ------------------------------------------------------------- checks
    def assert_consistent(self) -> None:
        """Invariants the property tests lean on: counts never negative, the
        free list is exactly the zero-refcount pages, no duplicates."""
        assert (self._refcounts >= 0).all(), "negative refcount"
        free = set(self._free)
        assert len(free) == len(self._free), "duplicate page in free list"
        zero = {i for i in range(self.num_pages) if self._refcounts[i] == 0}
        assert free == zero, f"free list {free} != zero-refcount pages {zero}"


# ----------------------------------------------------------------- helpers


def n_attn_layers(cfg: ModelConfig) -> int:
    return len(cfg.attention_layers)


def cache_bytes_per_token(cfg: ModelConfig, dtype_bytes: int = 2) -> int:
    """Communication load of C2C per generated/cached token (paper: 88 KB/token
    for the 4-transmitter case-study zoo). Counts k+v over all attention layers."""
    hd = cfg.resolved_head_dim
    n_attn = len(cfg.attention_layers)
    return 2 * n_attn * cfg.num_kv_heads * hd * dtype_bytes
