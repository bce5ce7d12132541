"""Continuous-batching federated serving engine (replaces lockstep serving).

The lockstep ``BatchedServer`` (launch/serve.py) needs every request to arrive
together, decode in unison and finish together — and its fused-prefix path
re-jits a fresh serve step per call. This engine serves the regime the paper's
federation actually targets: heavy *mixed* traffic, where standalone, C2C-fused
and T2T requests with different lengths and arrival times share one device.

Design:

- **Slot table** — a fixed-capacity decode cache whose batch axis is
  ``max_slots`` request slots, each with its own position (the per-slot
  ``pos`` vector ``transformer.decode_step`` understands). Two layouts:

  * *dense* (default): ``models/cache.KVCache.init_slots`` — every slot owns a
    full ``max_seq`` row. Simple, and the byte-identity reference.
  * *paged* (``paged=True``): ``models/cache.SlotTable`` — K/V pages live in a
    shared pool; each slot maps ``ceil(tokens/page_size)`` physical pages. At
    a fixed pool budget (``num_pages``) the engine sustains far more
    concurrent slots than dense whenever requests are shorter than
    ``max_seq`` — benchmarks/engine_bench.py shows ≥2× at equal HBM with
    byte-identical decode outputs. Pages are allocated host-side at admission
    (enough for prompt + max_new_tokens, so decode never allocates) and
    returned to the free list on completion. The decode step attends
    **in place**: the paged Pallas kernel (kernels/paged_attention.py) walks
    each slot's page map with scalar prefetch, reading only the pages that
    hold live tokens — no per-step ``dense_view()`` gather, no ``commit()``
    scatter-back (``paged_attention="gather"`` keeps the old gathered-view
    path as the debug/parity reference; engine_bench pins the two paths
    token-identical and reports the HBM bytes saved).

- **Admission queue** — ``submit()`` enqueues; each ``step()`` first admits
  queued requests into free slots, so requests join mid-flight without
  disturbing in-flight neighbours. With ``admit_batch > 1``, up to that many
  same-bucket-length requests share ONE prefill forward (batch-admission
  prefill); the prefill always runs at batch width ``admit_batch`` (short
  batches padded with zero-token rows, whose outputs are discarded — safe
  because inference MoE is dropless, so pad rows can't steal capacity), so
  it still traces once per prompt bucket.

- **Completion path** — a slot is freed the step its request finishes; stale
  K/V are masked by the per-slot position, so no zeroing is needed and the
  slot is immediately reusable.

- **One jitted decode step** — the whole slot array decodes in a single jitted
  function with *fixed* shapes: ``max_slots`` rows, ``max_seq`` cache (paged:
  the gathered page view), and a per-slot fused C2C prefix padded to a fixed
  ``max_prefix`` bucket whose absent/inactive positions carry
  ``PREFIX_MASK_BIAS`` (zero attention mass). The step therefore traces
  exactly once, no matter how the standalone / C2C-fused / T2T request mix
  changes (``stats["decode_traces"]`` proves it).

- **Page sharing (paged only, ``prefix_cache=True``)** — page bookkeeping is
  owned by a typed, refcounted ``models/cache.PageAllocator`` (the engine
  holds ``PageLease`` handles, never raw page-id lists), and admission
  consults a ``launch/prefix_cache.RadixPrefixIndex`` mapping (fused digest,
  prompt tokens) → already-cached physical pages. On a hit the new slot
  *shares* the matched pages (read-only) and prefills only the suffix — the
  cached prefix is gathered into ``extra_kv`` and RoPE positions are shifted
  by ``pos_offset`` — so a shared-system-prompt workload admits with a
  fraction of the pages and prefill FLOPs (benchmarks/engine_bench.py's
  shared-prefix section shows ≥2× concurrent slots at byte-identical
  outputs). A partially-matched page is copy-on-write: the allocator's
  ``cow`` fault swaps the share for a private copy before the suffix's first
  divergent token write lands in it. Fused C2C prefixes are shared by
  *digest*: the per-slot fused table became a row table with host-side row
  indirection, so a prefix a peer transmitted once is inserted once and every
  later request fusing the same digest just points its slot at that row.

- **Chunked prefill (paged only, ``prefill_token_budget=N``)** — a monolithic
  prefill of a long prompt stalls every in-flight decode behind one huge
  forward (the long-prompt p99 tail). With a token budget set, admission only
  *reserves* a slot + page lease; each ``step()`` then spends at most ``N``
  prompt tokens — across the oldest partially-prefilled prompts — before
  decoding, so decode latency is bounded by the budget, not the longest
  prompt. Chunks run through ``transformer.prefill_chunk``: K/V scatter
  straight into the lease's pool pages and the ragged varlen flash-prefill
  kernel (kernels/prefill_attention.py) attends causally over radix-shared
  prefix pages, earlier chunks and the current chunk in one pass — no dense
  staging cache, no ``extra_kv`` prefix gather. The call width is always
  exactly ``N`` (ragged tails padded with dead rows the kernel zero-masks),
  so chunked prefill traces ONCE per engine regardless of prompt lengths or
  chunk counts. Mid-prefill the slot is invisible to decode: its device
  page-map row stays INVALID (decode writes drop) until the final chunk
  adopts the lease row (``SlotTable.adopt_slot``) and publishes the first
  generated token. Radix hits still share matched pages (CoW on a partial
  page) at reservation time — only the unmatched tail is chunked.

- **Sanitizer (paged only, ``sanitize=True``)** — the allocator is built as
  ``analysis/sanitizer.PageSanitizer``, a PageAllocator subclass carrying
  per-page shadow holders with grant-site provenance. The engine reports
  every device write it issues (``note_write``: prefill inserts, suffix
  scatters, CoW copies, per-step decode writes) and hands over its device
  state after each step (``check_step``); ``drain()`` raises on a non-empty
  leak report. Leaks, double-releases, evict-while-shared and
  shared-writes-without-CoW surface at the offending step, named by the
  allocation site — with ``sanitize=False`` (default) no sanitizer exists
  and decode outputs are byte-identical either way.

Prefill is bucketed separately (``prompt_bucket``): right-padding a prompt is
exact for *full-attention* layers (causality — pad keys sit after every real
query, and the per-slot position mask hides them). It is NOT exact for
sliding-window ring buffers (pad writes can wrap the ring and evict real
in-window entries) or recurrent/SSD state (carried left-to-right through
pads), so models with swa/rec/ssd layers prefill at the exact prompt length
instead. Paged mode likewise requires a pure full-attention model (stateful
layers have O(1)-per-slot cost — nothing to page).

Quickstart::

    eng = ContinuousBatchingEngine(cfg, params, max_slots=8, max_seq=128,
                                   max_prefix=16)
    rid_a = eng.submit(prompt_a, max_new_tokens=16)               # standalone
    rid_b = eng.submit(prompt_b, max_new_tokens=8, fused=prefix)  # C2C-fused
    done = eng.drain()      # or eng.step() per tick for online serving

    # paged: 32 slots over a 16-slot-equivalent page pool
    eng = ContinuousBatchingEngine(cfg, params, max_slots=32, max_seq=128,
                                   paged=True, page_size=16,
                                   num_pages=16 * 128 // 16)
"""
from __future__ import annotations

import math
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.sanitizer import PageSanitizer, SanitizerError
from repro.configs.base import ModelConfig
from repro.models import transformer as T
from repro.models.cache import (FusedPrefix, KVCache, PageAllocator,
                                PageLease, SlotTable, fused_digest)
from repro.launch.prefix_cache import PrefixMatch, RadixPrefixIndex


@dataclass
class EngineRequest:
    """One queued request. ``fused`` is an already-projected C2C prefix
    (models/cache.FusedPrefix, shapes (n_attn_rx, 1, Hkv, Sf, hd)) with
    Sf <= the engine's ``max_prefix`` (see core/c2c.fused_prefix).
    ``digest`` is the fused prefix's content digest (None for standalone) —
    the identity under which fused rows and prompt pages are shared."""

    rid: int
    prompt: jax.Array  # (1, S) int32
    max_new_tokens: int
    fused: Optional[FusedPrefix] = None
    protocol: str = "standalone"
    meta: dict = field(default_factory=dict)
    digest: Optional[str] = None
    submitted: float = 0.0  # time.perf_counter() at the queue append


@dataclass
class Completion:
    rid: int
    tokens: np.ndarray  # (max_new_tokens,) int32 greedy continuation
    protocol: str
    meta: dict = field(default_factory=dict)


@dataclass
class _PartialPrefill:
    """One prompt mid-chunked-prefill: slot + lease reserved, prompt tokens
    ``[0, done)`` already resident in the lease's pages (a radix-shared
    prefix counts), the slot still inactive and its device page-map row
    still INVALID until the final chunk adopts it."""

    req: EngineRequest
    slot: int
    lease: PageLease
    row: np.ndarray  # (pages_per_slot,) int32 lease page row, INVALID-padded
    done: int        # tokens already resident (shared prefix + prior chunks)
    matched: int     # tokens served by the radix hit at reservation time
    host_prompt: np.ndarray  # (S,) int32 host copy: chunk slicing must not
    #                          pay a device sync per per-step chunk call


class ContinuousBatchingEngine:
    """Fixed-slot continuous-batching decode engine for one receiver model."""

    def __init__(
        self,
        cfg: ModelConfig,
        params: dict,
        *,
        max_slots: int = 8,
        max_seq: int = 128,
        max_prefix: int = 0,
        cache_dtype=jnp.float32,
        prompt_bucket: Optional[int] = None,
        admit_batch: int = 1,
        paged: bool = False,
        page_size: int = 16,
        num_pages: Optional[int] = None,
        paged_attention: str = "kernel",
        prefix_cache: bool = True,
        sanitize: bool = False,
        prefill_token_budget: Optional[int] = None,
    ):
        if max_prefix and not cfg.attention_layers:
            raise ValueError("fused prefixes need attention layers (C2C medium)")
        if admit_batch < 1:
            raise ValueError("admit_batch must be >= 1")
        if paged_attention not in ("kernel", "gather"):
            raise ValueError(f"paged_attention must be 'kernel' (in-place "
                             f"Pallas walk) or 'gather' (dense_view "
                             f"reference), got {paged_attention!r}")
        if sanitize and not paged:
            raise ValueError("sanitize=True checks page lifecycles and "
                             "needs paged=True (dense slots own no pages)")
        self.cfg, self.params = cfg, params
        self.max_slots, self.max_seq = max_slots, max_seq
        self.max_prefix = max_prefix
        self.cache_dtype = cache_dtype
        self.admit_batch = admit_batch
        self.paged = paged
        self.page_size = page_size
        self.paged_attention = paged_attention
        # exact-length prefill unless the model is pure full-attention:
        # right-padded prompts pollute rec/ssd left-to-right state, and pad
        # writes can wrap a swa ring buffer and evict real in-window entries
        pad_safe = all(k == "attn" for k in cfg.block_pattern)
        self.prompt_bucket = prompt_bucket if pad_safe else None

        if prefill_token_budget is not None:
            if prefill_token_budget < 1:
                raise ValueError("prefill_token_budget must be >= 1")
            if not paged:
                raise ValueError("prefill_token_budget (chunked prefill) "
                                 "needs paged=True — chunks scatter straight "
                                 "into pool pages")
            if not pad_safe:
                raise ValueError("chunked prefill requires a pure "
                                 "full-attention block pattern; "
                                 f"{cfg.name} has {cfg.block_pattern}")
        self.prefill_budget = prefill_token_budget
        # the ragged kernel's query-block size must divide the chunk width;
        # one full-width block per chunk call minimises grid points (the
        # kernel masks dead rows, so a partial final chunk stays exact)
        self._chunk_bq = prefill_token_budget if prefill_token_budget else 0
        self._partials: "deque[_PartialPrefill]" = deque()

        self.prefix_cache = bool(prefix_cache and paged)

        if paged:
            # page pool + per-slot page maps; the typed PageAllocator is the
            # only authority over page ids (refcounts, sharing, CoW) — the
            # engine holds PageLease handles; device scatter/gather lives in
            # models/cache.SlotTable
            self._table = SlotTable.init(cfg, max_slots, max_seq, cache_dtype,
                                         page_size=page_size,
                                         num_pages=num_pages)
            # PageSanitizer IS a PageAllocator (analysis/sanitizer.py): same
            # refcounts plus shadow holder/provenance state the engine feeds
            # through note_write/check_step hooks below
            self._san: Optional[PageSanitizer] = (
                PageSanitizer(self._table.num_pages) if sanitize else None)
            self._allocator: Optional[PageAllocator] = (
                self._san if self._san is not None
                else PageAllocator(self._table.num_pages))
            self._allocator.holders_hook = self._pool_holders
            self._leases: Dict[int, PageLease] = {}
        else:
            self._table = KVCache.init_slots(cfg, max_slots, max_seq,
                                             cache_dtype)
            self._san = None
            self._allocator = None
        self._radix = (RadixPrefixIndex(page_size)
                       if self.prefix_cache else None)
        self._tok = jnp.zeros((max_slots,), jnp.int32)
        # Fused prefixes live in a ROW table (max_slots usable rows + one
        # permanently all-masked row at index max_slots for standalone slots);
        # each slot points at a row via the host-side _fused_rows indirection,
        # so requests sharing a digest share one inserted row.
        self._fused = (FusedPrefix.empty(cfg, max_slots + 1, max_prefix,
                                         cache_dtype)
                       if max_prefix else None)
        self._fused_rows = np.full(max_slots, max_slots, np.int64)
        self._fused_alloc = PageAllocator(max_slots)  # rows, refcounted
        self._fused_digest_rows: "OrderedDict[str, int]" = OrderedDict()
        # shared all-masked prefix for standalone admissions (identical every
        # time — build once, not per request)
        self._empty_req_fused = (FusedPrefix.empty(cfg, 1, max_prefix,
                                                   cache_dtype)
                                 if max_prefix else None)
        self._active = np.zeros(max_slots, bool)
        self._slot_rid: List[Optional[int]] = [None] * max_slots
        self._remaining = np.zeros(max_slots, np.int64)
        self._queue: deque = deque()
        self._outputs: Dict[int, list] = {}
        self._req_info: Dict[int, EngineRequest] = {}
        self._ready: List[Completion] = []  # completed at admission (1-token)
        self._next_rid = 0
        self.stats = {"decode_traces": 0, "prefill_traces": 0, "admitted": 0,
                      "completed": 0, "decode_steps": 0, "admit_batches": 0,
                      "peak_active": 0, "decode_view_gathers": 0,
                      "prefill_tokens": 0, "prefill_chunks": 0,
                      "suffix_prefill_traces": 0,
                      "shared_admits": 0, "radix_hits": 0,
                      "radix_matched_tokens": 0, "cow_copies": 0,
                      "fused_inserts": 0, "fused_digest_hits": 0,
                      # summed us from the queue append to leaving the
                      # queue (slot reserved or admitted), and how many left
                      "queue_wait_us": 0, "reserved": 0}
        self._decode = jax.jit(self._make_decode())
        self._prefill = jax.jit(self._make_prefill())
        if paged:
            self._insert = jax.jit(
                lambda table, slot, req, length, pages, bi:
                table.insert_slot(slot, req, length, pages, batch_index=bi))
        else:
            self._insert = jax.jit(
                lambda table, slot, req, length, bi:
                table.insert_slot(slot, req, length, batch_index=bi))
        self._insert_fused = jax.jit(
            lambda table, slot, req: table.insert_slot(slot, req))
        if self.prefix_cache:
            self._suffix_prefill = jax.jit(self._make_suffix_prefill())
            self._copy_page = jax.jit(
                lambda table, src, dst: table.copy_page(src, dst))
        if self.prefill_budget:
            self._chunk_prefill = jax.jit(self._make_chunk_prefill())

    # ------------------------------------------------------------- jitted fns
    def _make_decode(self):
        cfg, paged = self.cfg, self.paged
        in_place = paged and self.paged_attention == "kernel"

        def decode(params, table, tok, fused, fused_rows, active):
            self.stats["decode_traces"] += 1  # trace-time compile count; lint: allow(trace-side-effect)
            ek = None
            if fused is not None:
                # row indirection: slots sharing a digest gather the same row
                # (standalone slots gather the permanently-masked empty row)
                sel = FusedPrefix(k=fused.k[:, fused_rows],
                                  v=fused.v[:, fused_rows],
                                  bias=fused.bias[:, fused_rows])
                ek = sel.to_extra_kv(cfg)
            if in_place:
                # paged hot loop: decode_step dispatches on the SlotTable and
                # walks page maps inside the Pallas kernel — no dense_view()
                # gather, no commit() scatter-back
                logits, new_table = T.decode_step(cfg, params, table, tok,
                                                  extra_kv=ek)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                nxt = jnp.where(active, nxt, tok)
                # hold inactive slots in place so their position never grows
                # past max_seq while they wait for the next occupant
                return nxt, new_table.with_pos(
                    jnp.where(active, new_table.pos, table.pos))
            if paged:  # gather reference path (debug/parity)
                self.stats["decode_view_gathers"] += 1  # lint: allow(trace-side-effect)
            view = table.dense_view() if paged else table
            logits, new_view = T.decode_step(cfg, params, view, tok,
                                             extra_kv=ek)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            nxt = jnp.where(active, nxt, tok)
            pos = jnp.where(active, new_view.pos, table.pos)
            if paged:
                # scatter this step's tokens back to their physical pages;
                # unmapped (inactive) slots are dropped by the scatter
                new_table = table.commit(new_view, pos)
            else:
                new_table = KVCache(pos=pos, layers=new_view.layers)
            return nxt, new_table

        return decode

    def _make_prefill(self):
        cfg, max_seq, dtype = self.cfg, self.max_seq, self.cache_dtype

        def prefill(params, tokens, fused):
            self.stats["prefill_traces"] += 1  # lint: allow(trace-side-effect)
            ek = fused.to_extra_kv(cfg) if fused is not None else None
            logits, cache = T.prefill(cfg, params, tokens, max_seq=max_seq,
                                      cache_dtype=dtype, extra_kv=ek)
            return logits, cache

        return prefill

    def _make_suffix_prefill(self):
        """Radix-hit admission: prefill only the prompt's uncached suffix.

        The matched prefix's KV is gathered from its (shared) pages into an
        ``extra_kv`` prefix — fixed gather width (pages_per_slot pages) with
        positions ≥ ``prefix_len`` masked at PREFIX_MASK_BIAS, so the fn
        traces once per suffix bucket. RoPE positions are shifted by
        ``prefix_len`` (transformer.prefill's ``pos_offset``); the suffix's
        K/V rows are scattered to their per-token (page, offset) targets and
        the slot adopts the full shared+fresh page row in one fused step."""
        cfg, dtype = self.cfg, self.cache_dtype

        def sprefill(params, table, toks, prefix_pages, prefix_len, fused,
                     phys, off, page_row, slot, final_pos):
            self.stats["suffix_prefill_traces"] += 1  # lint: allow(trace-side-effect)
            ek = table.prefix_extra_kv(prefix_pages, prefix_len)
            if fused is not None:
                # fused C2C prefix precedes the cached prompt prefix, same
                # order as the fresh prefill path
                fek = fused.to_extra_kv(cfg)
                ek = [FusedPrefix.concat([f, p])
                      if f is not None and p is not None else p
                      for f, p in zip(fek, ek)]
            logits, cache = T.prefill(cfg, params, toks,
                                      max_seq=int(toks.shape[1]),
                                      cache_dtype=dtype, extra_kv=ek,
                                      pos_offset=prefix_len)
            table = table.insert_suffix(slot, cache, phys, off, page_row,
                                        final_pos)
            return logits, table

        return sprefill

    def _make_chunk_prefill(self):
        """One token-budget chunk of one prompt, straight into pool pages.

        The call width is ALWAYS ``prefill_token_budget`` (ragged tails ride
        as dead rows: pad writes drop through INVALID page ids and the
        ragged kernel zero-masks their outputs), and every other operand is
        fixed-shape or a traced scalar — so the fn traces exactly once per
        engine no matter how prompt lengths, chunk counts or radix hits
        vary (``stats["prefill_traces"]`` counts it).

        All per-chunk operands ride in ONE packed int32 vector ``meta`` =
        [pos_offset, n_live, slot, adopt_len, page_row(pps), toks(C)]: a
        chunk call is a single host->device transfer plus a single
        dispatch, instead of six eager transfers — on the chunk scheduler's
        per-step hot path that overhead is comparable to the kernel
        itself."""
        cfg, bq = self.cfg, self._chunk_bq
        pps = self.max_seq // self.page_size

        def cprefill(params, table, tok, meta, fused):
            self.stats["prefill_traces"] += 1  # lint: allow(trace-side-effect)
            pos_offset, n_live = meta[0], meta[1]
            slot, adopt_len = meta[2], meta[3]
            page_row = meta[4:4 + pps]
            toks = meta[4 + pps:].reshape(1, -1)
            ek = fused.to_extra_kv(cfg) if fused is not None else None
            logits, table = T.prefill_chunk(cfg, params, table, toks,
                                            pos_offset, n_live, page_row,
                                            block_q=bq, extra_kv=ek)
            # greedy next token off the chunk's last live row, in-jit: only
            # the final chunk's value is used, but computing it here spares
            # the activation path an eager argmax dispatch per admission
            first = jnp.argmax(logits[0, n_live - 1]).astype(jnp.int32)
            # final chunk of a multi-token request (adopt_len = prompt
            # length, else 0): adopt the page row and install the first
            # token in one fused dispatch — an eager adopt + at[].set here
            # would add two device round-trips to every activation step
            adopt = adopt_len > 0
            table = jax.lax.cond(
                adopt,
                lambda t: t.adopt_slot(slot, page_row, adopt_len),
                lambda t: t, table)
            tok = jnp.where(adopt & (jnp.arange(tok.shape[0]) == slot),
                            first, tok)
            return first, tok, table

        return cprefill

    # ------------------------------------------------------------- submission
    def submit(self, prompt, max_new_tokens: int, *,
               fused=None, digest: Optional[str] = None,
               protocol: Optional[str] = None,
               meta: Optional[dict] = None) -> int:
        """Queue a request; returns its rid. Joins the running batch at the
        next step() with a free slot.

        ``digest`` names the fused prefix's content identity (computed from
        its bytes when omitted): requests sharing a digest share one inserted
        fused row, and — with the prefix cache — can share prompt pages."""
        rid = self._next_rid
        with jax.profiler.TraceAnnotation("fr.enqueue", rid=rid):
            prompt = jnp.asarray(prompt, jnp.int32)
            if prompt.ndim == 1:
                prompt = prompt[None]
            if prompt.shape[0] != 1:
                raise ValueError(
                    "submit() takes one request at a time (B=1)")
            if max_new_tokens < 1:
                raise ValueError("max_new_tokens must be >= 1")
            S = int(prompt.shape[1])
            if S >= self.max_seq:
                # checked before the combined bound so the degenerate case
                # gets its own name: bucket rounding (_bucket_len) clamps at
                # max_seq, and a prompt that large would land in a bucket
                # with zero headroom for even the first decoded token
                raise ValueError(
                    f"prompt({S}) fills the whole max_seq={self.max_seq} "
                    "cache: no headroom for the first decoded token")
            if S + max_new_tokens > self.max_seq:
                raise ValueError(f"prompt({S}) + gen({max_new_tokens}) "
                                 f"exceeds max_seq={self.max_seq}")
            # 1-token requests are answered at prefill and own no pages —
            # except under chunked prefill, which leases pages for the
            # prompt itself
            if self.paged and (max_new_tokens > 1 or self.prefill_budget):
                need = math.ceil((S + max_new_tokens - 1) / self.page_size)
                if need > self._table.num_pages:
                    raise ValueError(
                        f"request needs {need} pages but the pool only has "
                        f"{self._table.num_pages}; it could never be "
                        f"admitted")
            if fused is not None:
                if not self.max_prefix:
                    raise ValueError("engine built with max_prefix=0 "
                                     "cannot take fused prefixes")
                fused = self.held_prefix(fused)
                if digest is None:
                    digest = fused_digest(fused)
            else:
                digest = None  # standalone requests share the None root
            proto = protocol or ("c2c" if fused is not None else "standalone")
            self._next_rid += 1
            req = EngineRequest(rid, prompt, max_new_tokens, fused, proto,
                                meta or {}, digest, time.perf_counter())
            self._queue.append(req)
            self._req_info[rid] = req
            return rid

    def held_prefix(self, fused=None) -> FusedPrefix:
        """A request's fused prefix as the engine holds it: padded to
        ``max_prefix`` at the cache dtype, or the all-masked prefix of a
        standalone request (``fused=None``). One dtype for both, so the
        prefill steps trace once, and decode reads it from the cache-dtype
        row table anyway."""
        if fused is None:
            return self._empty_req_fused
        with jax.profiler.TraceAnnotation("fr.pad"):
            fused = FusedPrefix.ensure(fused).pad(self.max_prefix)
            return FusedPrefix(k=fused.k.astype(self.cache_dtype),
                               v=fused.v.astype(self.cache_dtype),
                               bias=fused.bias)

    # -------------------------------------------------------------- admission
    def _bucket_len(self, S: int) -> int:
        """Prefill width for an S-token prompt (or prompt suffix): rounded up
        to the bucket, clamped at ``max_seq``. The clamp is only sound while
        the *exact* length leaves decode headroom — a prompt of ``max_seq``
        itself would round into a bucket with zero room for the first decoded
        token, so that degenerate case is rejected here (submit() already
        refuses it with its own message; this guard covers direct callers)."""
        if S >= self.max_seq:
            raise ValueError(
                f"cannot bucket {S} token(s): max_seq={self.max_seq} leaves "
                "no headroom for the first decoded token")
        if self.prompt_bucket is None:
            return S
        b = ((S + self.prompt_bucket - 1) // self.prompt_bucket
             ) * self.prompt_bucket
        return min(b, self.max_seq)

    def _free_slots(self) -> List[int]:
        # a slot mid-chunked-prefill is inactive but reserved (_slot_rid set).
        # The queue's head takes slot rid % max_slots or the next free one
        # after it, so a lightly loaded engine still serves from every slot.
        n, start = self.max_slots, self._queue[0].rid
        return [i % n for i in range(start, start + n)
                if not self._active[i % n] and self._slot_rid[i % n] is None]

    def _pages_needed(self, req: EngineRequest) -> int:
        # Highest position ever *written* is S + max_new - 2 (the final
        # generated token is emitted, never cached), so pages must cover
        # S + max_new - 1 slots. Bucket padding beyond S never becomes
        # visible (the position mask hides [S, ·), and decode rewrites each
        # index — in the gathered view, before attention — the step it first
        # would be exposed), so unallocated tail pages are never read.
        S = int(req.prompt.shape[1])
        return math.ceil((S + req.max_new_tokens - 1) / self.page_size)

    def _radix_match(self, req: EngineRequest) -> Optional[PrefixMatch]:
        """Longest cached-prefix match for a slot-taking request (1-token
        requests are answered at prefill and never own pages)."""
        if self._radix is None or req.max_new_tokens <= 1:
            return None
        return self._radix.lookup(req.digest, np.asarray(req.prompt[0]))

    def _defer_for_sharing(self, head: EngineRequest,
                           req: EngineRequest) -> bool:
        """True when a queued request should sit out this *fresh* admission
        batch because a later _admit pass can admit it shared: it already has
        a radix hit, or it shares a leading prefix (same fused digest) with
        the head about to register its pages."""
        if req.max_new_tokens <= 1:
            return False
        if head.max_new_tokens > 1 and req.digest == head.digest:
            ta = np.asarray(head.prompt[0])
            tb = np.asarray(req.prompt[0])
            # any nonzero lcp can match after head registers (full-page nodes
            # share in place; partials extend via CoW)
            if tb.size > 1 and ta.size and int(ta[0]) == int(tb[0]):
                return True
        return self._radix.lookup(req.digest,
                                  np.asarray(req.prompt[0])) is not None

    def _take_admission_batch(self, n_free: int) -> List[EngineRequest]:
        """Pop up to ``admit_batch`` same-bucket-length requests that fit the
        free slots (and, paged, the free page pool). FIFO at the head: if the
        front request cannot be placed, nothing is admitted this step. With
        the prefix cache on, requests that could share the head's pages are
        left queued for a shared admission on a later pass this same step."""
        if not self._queue:
            return []
        head = self._queue[0]
        Sb = self._bucket_len(int(head.prompt.shape[1]))
        pages_left = 0
        if self.paged:
            assert self._allocator is not None
            pages_left = self._allocator.num_free
        batch: List[EngineRequest] = []
        taken_idx: List[int] = []
        for i, req in enumerate(self._queue):
            if len(batch) == self.admit_batch:
                break
            if self._bucket_len(int(req.prompt.shape[1])) != Sb:
                if i == 0:
                    return []  # unreachable (head defines Sb), kept for shape
                continue
            if self._radix is not None and i > 0 and \
                    self._defer_for_sharing(head, req):
                continue
            takes_slot = req.max_new_tokens > 1
            if takes_slot and n_free - sum(
                    r.max_new_tokens > 1 for r in batch) <= 0:
                break
            if self.paged and takes_slot:
                need = self._pages_needed(req)
                if need > pages_left:
                    if i == 0:
                        return []  # head-of-line blocked on pages: wait
                    continue
                pages_left -= need
            batch.append(req)
            taken_idx.append(i)
        for i in reversed(taken_idx):
            del self._queue[i]
        for req in batch:
            self._left_queue(req)
        return batch

    def _left_queue(self, req: EngineRequest) -> None:
        """Count ``req``'s wait in the queue, now that it left it."""
        self.stats["queue_wait_us"] += round(
            (time.perf_counter() - req.submitted) * 1e6)
        self.stats["reserved"] += 1

    def _ensure_pages(self, need: int) -> bool:
        """Make ``need`` pages allocatable, evicting LRU prefix-index entries
        under pool pressure (only pages no slot still maps actually free)."""
        assert self._allocator is not None
        if self._allocator.can_alloc(need):
            return True
        if self._radix is not None:
            self._radix.evict(self._allocator, need - self._allocator.num_free)
        return self._allocator.can_alloc(need)

    def _pool_holders(self) -> str:
        """Who holds the page pool right now — attached to the allocator's
        pool-exhaustion RuntimeError (``PageAllocator.holders_hook``) so an
        admission failure names the slots, index pins and (under
        ``sanitize=True``) the grant sites responsible."""
        lines: List[str] = []
        for s in sorted(self._leases):
            lease = self._leases[s]
            lines.append(f"  slot {s} (rid={self._slot_rid[s]}): "
                         f"{lease.num_pages} page(s)")
        if self._radix is not None:
            for name, n in sorted(self._radix.pin_summary().items()):
                lines.append(f"  prefix index [{name[:16]}]: "
                             f"{n} pinned page(s)")
        if self._san is not None:
            detail = self._san.describe_holders()
            if detail:
                lines.append("  sanitizer grant sites:")
                lines.extend("  " + ln for ln in detail.splitlines())
        return "\n".join(lines)

    def _register_prefix(self, req: EngineRequest, lease: PageLease) -> None:
        """Publish an admitted prompt's pages to the radix index (pins them,
        so they outlive the slot). Keyed by the request's fused digest —
        prompt KV depends on the fused prefix attended during prefill."""
        if self._radix is None:
            return
        self._radix.register(req.digest, np.asarray(req.prompt[0]),
                             lease.ids(), self._allocator)

    def _assign_fused_row(self, slot: int, req: EngineRequest) -> None:
        """Point ``slot`` at its fused row: the permanently-masked empty row
        for standalone requests; otherwise the digest's existing row (one
        insert amortized over every sharer) or a freshly inserted one."""
        if self._fused is None:
            return
        if req.fused is None:
            self._fused_rows[slot] = self.max_slots
            return
        row = self._fused_digest_rows.get(req.digest)
        if row is not None:
            self._fused_alloc.share([row])  # the slot's reference
            self._fused_digest_rows.move_to_end(req.digest)
            self.stats["fused_digest_hits"] += 1
        else:
            if not self._fused_alloc.can_alloc(1):
                self._evict_fused_rows(1)
            row = self._fused_alloc.alloc(1)[0]
            self._fused = self._insert_fused(self._fused, jnp.int32(row),
                                             req.fused)
            self._fused_alloc.retain(row)  # the digest table's pin
            self._fused_digest_rows[req.digest] = row
            self.stats["fused_inserts"] += 1
        self._fused_rows[slot] = row

    def _evict_fused_rows(self, want: int) -> None:
        """Drop LRU digest pins whose row no active slot references. Always
        succeeds for ``want=1``: rows ≥ max_slots ≥ active slots, so some
        digest is always pin-only when the row pool is full."""
        for digest in list(self._fused_digest_rows):
            if self._fused_alloc.num_free >= want:
                return
            row = self._fused_digest_rows[digest]
            if self._fused_alloc.refcount(row) == 1:  # pin only
                self._fused_alloc.release([row])
                del self._fused_digest_rows[digest]

    def _admit_shared(self, req: EngineRequest, slot: int,
                      match: PrefixMatch) -> bool:
        """Admit one radix-hit request: share the matched pages, CoW-copy a
        partially-matched page, prefill only the suffix. Returns False if the
        pool can't cover the request's unshared pages (head-of-line waits)."""
        S = int(req.prompt.shape[1])
        pg = self.page_size
        P = match.matched  # tokens served from cache (≤ S - 1)
        total = self._pages_needed(req)
        shared_ids = list(match.page_ids)
        cow_idx = None
        if match.partial_page is not None:
            shared_ids.append(match.partial_page)
            cow_idx = len(shared_ids) - 1
        fresh = total - len(shared_ids)
        if not self._ensure_pages(fresh + (1 if cow_idx is not None else 0)):
            return False
        assert self._allocator is not None
        lease = self._allocator.lease(shared=shared_ids, fresh=fresh)
        if self._san is not None:
            self._san.annotate(lease, slot=slot, rid=req.rid,
                               digest=req.digest)
        if cow_idx is not None:
            # the suffix prefill writes position P inside the partially
            # matched page — its first divergent token write — so the CoW
            # fault copies that page before the slot maps it writable
            src, dst = self._allocator.cow(lease, cow_idx)
            self._table = self._copy_page(self._table, jnp.int32(src),
                                          jnp.int32(dst))
            if self._san is not None:
                self._san.note_write([dst], lease, what="cow page copy")
            self.stats["cow_copies"] += 1
        pps, invalid = self._table.pages_per_slot, self._table.invalid_page
        row = lease.page_row(pps, invalid)
        # prefix gather reads the slot's own row: shared full pages plus the
        # CoW copy (same bytes as its source), INVALID-padded to fixed width
        n_prefix_pages = math.ceil(P / pg)
        prefix_pages = np.full(pps, invalid, np.int32)
        prefix_pages[:n_prefix_pages] = row[:n_prefix_pages]

        Ssuf = S - P
        Sb = self._bucket_len(Ssuf)
        toks = jnp.pad(req.prompt[:, P:], ((0, 0), (0, Sb - Ssuf)))
        # per-token scatter targets: suffix row i holds absolute position
        # P + i → page (P+i)//pg at offset (P+i)%pg; pad rows drop (INVALID)
        abs_pos = P + np.arange(Sb)
        page_idx = np.minimum(abs_pos // pg, pps - 1)
        phys = np.where(abs_pos < S, row[page_idx], invalid).astype(np.int32)
        off = (abs_pos % pg).astype(np.int32)
        if self._san is not None:
            # the suffix scatter must only touch pages the lease OWNS: fresh
            # pages and the CoW copy, never the shared full-prefix pages
            self._san.note_write(np.unique(phys[phys != invalid]), lease,
                                 what=f"suffix prefill (slot {slot})")

        rf = req.fused if req.fused is not None else self._empty_req_fused
        logits, self._table = self._suffix_prefill(
            self.params, self._table, toks, jnp.asarray(prefix_pages),
            jnp.int32(P), rf, jnp.asarray(phys), jnp.asarray(off),
            jnp.asarray(row), jnp.int32(slot), jnp.int32(S))
        first = jnp.argmax(logits[0, Ssuf - 1]).astype(jnp.int32)

        self._leases[slot] = lease
        self._outputs[req.rid] = [first]
        self._tok = self._tok.at[slot].set(first)
        self._assign_fused_row(slot, req)
        self._active[slot] = True
        self._slot_rid[slot] = req.rid
        self._remaining[slot] = req.max_new_tokens - 1
        self._register_prefix(req, lease)
        self.stats["admitted"] += 1
        self.stats["shared_admits"] += 1
        self.stats["radix_hits"] += 1
        self.stats["radix_matched_tokens"] += P
        self.stats["prefill_tokens"] += Ssuf
        self.stats["peak_active"] = max(self.stats["peak_active"],
                                        int(self._active.sum()))
        return True

    def _admit(self) -> None:
        while self._queue:
            free = deque(self._free_slots())
            if not free:
                break
            head = self._queue[0]
            match = self._radix_match(head)
            if match is not None:
                if not self._admit_shared(head, free[0], match):
                    break  # pool can't cover the unshared suffix: wait
                self._left_queue(self._queue.popleft())
                continue
            if self.paged and head.max_new_tokens > 1:
                # pool pressure may be index pins, not live slots — evict
                # LRU prefix entries so a fresh head is never starved
                self._ensure_pages(self._pages_needed(head))
            batch = self._take_admission_batch(len(free))
            if not batch:
                break
            Sb = self._bucket_len(int(batch[0].prompt.shape[1]))
            B = self.admit_batch
            toks = jnp.concatenate(
                [jnp.pad(r.prompt, ((0, 0), (0, Sb - r.prompt.shape[1])))
                 for r in batch]
                + [jnp.zeros((B - len(batch), Sb), jnp.int32)], axis=0)
            fused_b = None
            if self.max_prefix:
                # standalone members ride the same prefill trace as fused ones
                per_req = [r.fused if r.fused is not None
                           else self._empty_req_fused for r in batch]
                per_req += [self._empty_req_fused] * (B - len(batch))
                fused_b = FusedPrefix(
                    k=jnp.concatenate([f.k for f in per_req], axis=1),
                    v=jnp.concatenate([f.v for f in per_req], axis=1),
                    bias=jnp.concatenate([f.bias for f in per_req], axis=1))
            logits, cache_b = self._prefill(self.params, toks, fused_b)
            self.stats["admit_batches"] += 1
            for b, req in enumerate(batch):
                S = int(req.prompt.shape[1])
                first = jnp.argmax(logits[b, S - 1]).astype(jnp.int32)
                self._outputs[req.rid] = [first]
                self.stats["admitted"] += 1
                self.stats["prefill_tokens"] += S
                if req.max_new_tokens == 1:  # done at prefill: no slot taken
                    self._ready.append(self._finish(req.rid))
                    continue
                slot = free.popleft()
                if self.paged:
                    assert self._allocator is not None
                    lease = self._allocator.lease(fresh=self._pages_needed(req))
                    if self._san is not None:
                        self._san.annotate(lease, slot=slot, rid=req.rid,
                                           digest=req.digest)
                        self._san.note_write(lease.ids(), lease,
                                             what=f"prefill insert "
                                                  f"(slot {slot})")
                    self._leases[slot] = lease
                    row = lease.page_row(self._table.pages_per_slot,
                                         self._table.invalid_page)
                    self._table = self._insert(
                        self._table, jnp.int32(slot), cache_b, jnp.int32(S),
                        jnp.asarray(row), jnp.int32(b))
                    self._register_prefix(req, lease)
                else:
                    self._table = self._insert(
                        self._table, jnp.int32(slot), cache_b, jnp.int32(S),
                        jnp.int32(b))
                self._tok = self._tok.at[slot].set(first)
                self._assign_fused_row(slot, req)
                self._active[slot] = True
                self._slot_rid[slot] = req.rid
                self._remaining[slot] = req.max_new_tokens - 1
            self.stats["peak_active"] = max(self.stats["peak_active"],
                                            int(self._active.sum()))

    # ------------------------------------------------------- chunked prefill
    def _begin_partial(self, req: EngineRequest, slot: int, lease: PageLease,
                       *, done: int, matched: int) -> None:
        """Reserve ``slot`` for a chunked admission: the lease is held (and
        visible to the sanitizer's leak report) from reservation on, but the
        slot stays inactive and its device page row INVALID until the final
        chunk adopts it."""
        row = lease.page_row(self._table.pages_per_slot,
                             self._table.invalid_page)
        self._leases[slot] = lease
        self._slot_rid[slot] = req.rid
        self._partials.append(_PartialPrefill(req, slot, lease,
                                              np.asarray(row, np.int32),
                                              done, matched,
                                              np.asarray(req.prompt[0],
                                                         np.int32)))

    def _reserve_fresh(self, req: EngineRequest, slot: int) -> bool:
        """Reserve pages for a chunked admission with no cached prefix."""
        need = self._pages_needed(req)
        if not self._ensure_pages(need):
            return False
        assert self._allocator is not None
        lease = self._allocator.lease(fresh=need)
        if self._san is not None:
            self._san.annotate(lease, slot=slot, rid=req.rid,
                               digest=req.digest)
        self._begin_partial(req, slot, lease, done=0, matched=0)
        return True

    def _reserve_shared(self, req: EngineRequest, slot: int,
                        match: PrefixMatch) -> bool:
        """Reserve a radix-hit chunked admission: share the matched pages,
        CoW-copy a partially matched one (the first chunk writes position
        ``matched`` inside it), lease fresh pages for the rest. Only the
        unmatched tail will be chunked."""
        P = match.matched
        total = self._pages_needed(req)
        shared_ids = list(match.page_ids)
        cow_idx = None
        if match.partial_page is not None:
            shared_ids.append(match.partial_page)
            cow_idx = len(shared_ids) - 1
        fresh = total - len(shared_ids)
        if not self._ensure_pages(fresh + (1 if cow_idx is not None else 0)):
            return False
        assert self._allocator is not None
        lease = self._allocator.lease(shared=shared_ids, fresh=fresh)
        if self._san is not None:
            self._san.annotate(lease, slot=slot, rid=req.rid,
                               digest=req.digest)
        if cow_idx is not None:
            src, dst = self._allocator.cow(lease, cow_idx)
            self._table = self._copy_page(self._table, jnp.int32(src),
                                          jnp.int32(dst))
            if self._san is not None:
                self._san.note_write([dst], lease, what="cow page copy")
            self.stats["cow_copies"] += 1
        self.stats["radix_hits"] += 1
        self.stats["radix_matched_tokens"] += P
        self._begin_partial(req, slot, lease, done=P, matched=P)
        return True

    def _defer_for_partial(self, req: EngineRequest) -> bool:
        """True when the queue head should wait: an in-flight partial with
        the same digest and leading token will register a shareable prefix
        at its final chunk (the chunked analogue of _defer_for_sharing —
        partials progress every step, so the wait is bounded)."""
        if self._radix is None or req.max_new_tokens <= 1:
            return False
        tb = np.asarray(req.prompt[0])
        for part in self._partials:
            ta = part.host_prompt
            if part.req.digest == req.digest and tb.size > 1 and ta.size \
                    and int(ta[0]) == int(tb[0]):
                return True
        return False

    def _admit_chunked(self) -> None:
        """Chunked admission: FIFO-reserve slots + page leases for queued
        prompts. No prefill compute happens here — _run_chunks spends the
        per-step token budget on the oldest reservations."""
        while self._queue:
            free = self._free_slots()
            if not free:
                break
            head = self._queue[0]
            match = self._radix_match(head)
            if match is None and self._defer_for_partial(head):
                break
            ok = (self._reserve_shared(head, free[0], match)
                  if match is not None else
                  self._reserve_fresh(head, free[0]))
            if not ok:
                break  # head-of-line blocked on pages: wait for evictions
            self._left_queue(self._queue.popleft())

    def _run_chunks(self) -> None:
        """Spend up to ``prefill_token_budget`` prompt tokens on the oldest
        partial prefills (leftover budget rolls into the next partial — the
        calls all share one trace). A prompt's final chunk activates it; at
        most one prompt activates per step, so adoption cost (page-row
        adopt + first-token install) is bounded per step the same way the
        token budget bounds prefill compute — a backlog of small partials
        drains one per step instead of bursting into a single stall."""
        if not self.prefill_budget:
            return
        C = self.prefill_budget
        pg, invalid = self.page_size, self._table.invalid_page
        pps = self.max_seq // pg
        left = C
        while left > 0 and self._partials:
            part = self._partials[0]
            req = part.req
            S = int(req.prompt.shape[1])
            n = min(left, S - part.done)
            left -= n
            if self._san is not None:
                # the chunk's scatter only touches pages the lease OWNS:
                # shared full-prefix pages all sit before done//pg, and the
                # page holding position `done` is the CoW copy or fresh
                pages = part.row[part.done // pg:(part.done + n - 1) // pg + 1]
                self._san.note_write(np.unique(pages[pages != invalid]),
                                     part.lease,
                                     what=f"chunk prefill (slot {part.slot})")
            with jax.profiler.TraceAnnotation("fr.chunk", rid=req.rid,
                                              start=part.done, rows=n):
                rf = (req.fused if req.fused is not None
                      else self._empty_req_fused)
                final = part.done + n == S
                adopt_len = S if final and req.max_new_tokens > 1 else 0
                meta = np.zeros(4 + pps + C, np.int32)
                meta[:4] = (part.done, n, part.slot, adopt_len)
                meta[4:4 + pps] = part.row
                meta[4 + pps:4 + pps + n] = \
                    part.host_prompt[part.done:part.done + n]
                first, self._tok, self._table = self._chunk_prefill(
                    self.params, self._table, self._tok, jnp.asarray(meta),
                    rf)
                part.done += n
                self.stats["prefill_tokens"] += n
                self.stats["prefill_chunks"] += 1
                if part.done == S:
                    self._partials.popleft()
                    self._activate_partial(part, first)
                    break  # one adoption per step: keep the stall flat

    def _activate_partial(self, part: _PartialPrefill, first) -> None:
        """A prompt's final chunk landed: book-keep its activation. The
        device-side work — page-row adoption and first-token install — was
        fused into the final chunk call itself; ``first`` is the chunk jit's
        in-jit argmax. A 1-token request completes here instead (its page
        row was never adopted: the radix registration keeps the pages)."""
        req, slot = part.req, part.slot
        self._outputs[req.rid] = [first]
        self._register_prefix(req, part.lease)
        self.stats["admitted"] += 1
        if part.matched:
            self.stats["shared_admits"] += 1
        if req.max_new_tokens == 1:
            # answered by the final chunk: drop the reservation — the radix
            # registration above keeps the pages pinned for future sharers
            del self._leases[slot]
            assert self._allocator is not None
            self._allocator.release(part.lease)
            self._slot_rid[slot] = None
            self._ready.append(self._finish(req.rid))
            return
        self._assign_fused_row(slot, req)
        self._active[slot] = True
        self._remaining[slot] = req.max_new_tokens - 1
        self.stats["peak_active"] = max(self.stats["peak_active"],
                                        int(self._active.sum()))

    # ------------------------------------------------------------- completion
    def _finish(self, rid: int) -> Completion:
        req = self._req_info.pop(rid)
        # host-side stack: jnp.stack here would eagerly compile a fresh XLA
        # stack per distinct token count, a multi-ms stall on the step that
        # completes a request (the entries are host scalars already, bar the
        # first token, which np.asarray converts per element)
        toks = np.asarray([np.asarray(t) for t in self._outputs.pop(rid)],
                          np.int32)
        self.stats["completed"] += 1
        return Completion(rid, toks, req.protocol, req.meta)

    def _evict(self, slot: int) -> None:
        self._table = self._table.evict_slot(slot)
        if self.paged:
            assert self._allocator is not None
            lease = self._leases.pop(slot, None)
            if lease is not None:
                # refcounted: pages another sharer (or the prefix index)
                # still holds stay alive; exclusively-owned pages free now
                self._allocator.release(lease)
        if self._fused is not None:
            row = int(self._fused_rows[slot])
            if row != self.max_slots:
                self._fused_alloc.release([row])
                self._fused_rows[slot] = self.max_slots

    # ------------------------------------------------------------------ step
    def step(self) -> List[Completion]:
        """Admit what fits, decode one token for every active slot, free any
        slot whose request just finished. Returns the completions.

        Chunked mode (``prefill_token_budget``) replaces monolithic admission
        prefills with a reservation pass plus at most one token-budget's
        worth of chunk compute, so the decode cadence below stays bounded."""
        with jax.profiler.TraceAnnotation("fr.step"):
            with jax.profiler.TraceAnnotation("fr.admit"):
                if self.prefill_budget:
                    self._admit_chunked()
                else:
                    self._admit()
            self._run_chunks()
            done, self._ready = self._ready, []
            if not self._active.any():
                return done
            with jax.profiler.TraceAnnotation("fr.decode"):
                self._tok, self._table = self._decode(*self._decode_args())
            self.stats["decode_steps"] += 1
            with jax.profiler.TraceAnnotation("fr.sync"):
                tok_host = np.asarray(self._tok)
            if self._san is not None:
                # the decode step scattered each active slot's new token into
                # page pos//page_size at the slot's pre-increment position —
                # validate those writes before evictions release any lease
                pos_host = np.asarray(self._table.pos)
                for s in np.nonzero(self._active)[0]:
                    lease = self._leases[int(s)]
                    idx = (int(pos_host[s]) - 1) // self.page_size
                    if idx >= lease.num_pages:
                        raise SanitizerError(
                            f"decode wrote position {int(pos_host[s]) - 1} "
                            f"of slot {int(s)}, past its lease of "
                            f"{lease.num_pages} page(s)")
                    self._san.note_write([int(lease.page_ids[idx])], lease,
                                         what=f"decode write (slot {int(s)})")
            with jax.profiler.TraceAnnotation("fr.retire"):
                for s in np.nonzero(self._active)[0]:
                    rid = self._slot_rid[s]
                    self._outputs[rid].append(tok_host[s])
                    self._remaining[s] -= 1
                    if self._remaining[s] == 0:
                        self._active[s] = False
                        self._slot_rid[s] = None
                        self._evict(int(s))
                        done.append(self._finish(rid))
            if self._san is not None:
                # allocator / shadow-state / device page-map agreement, after
                # this step's admissions, decode writes and evictions landed
                self._san.check_step(np.asarray(self._table.page_map),
                                     self._active, self._leases,
                                     self._table.invalid_page)
            return done

    # ----------------------------------------------------------------- drain
    def drain(self) -> List[Completion]:
        """Run until the queue, partial prefills and every slot are empty."""
        out: List[Completion] = []
        while self._queue or self._partials or self._active.any():
            out.extend(self.step())
        out.extend(self._ready)
        self._ready = []
        if self._san is not None:
            report = self._san.leak_report(self._leases)
            if report:
                raise SanitizerError(
                    "page leak(s) at drain:\n"
                    + "\n".join("  " + line for line in report))
        return out

    def sanitizer_report(self) -> List[str]:
        """Outstanding page grants the sanitizer cannot attribute to a live
        slot (empty when clean or when built with ``sanitize=False``)."""
        if self._san is None:
            return []
        return self._san.leak_report(self._leases)

    # ----------------------------------------------------------------- intro
    @property
    def num_active(self) -> int:
        return int(self._active.sum())

    @property
    def num_queued(self) -> int:
        return len(self._queue)

    @property
    def num_partial(self) -> int:
        """Prompts reserved for chunked prefill but not yet fully resident."""
        return len(self._partials)

    def lower_decode(self):
        """The jitted decode step lowered for the engine's current state —
        the program every ``step()`` dispatches (``.as_text()`` shows whether
        the Pallas kernels compiled or were interpreted). Lowering after the
        first step reuses its trace: ``stats["decode_traces"]`` stays put."""
        return self._decode.lower(*self._decode_args())

    def _decode_args(self) -> tuple:
        fused_rows = (jnp.asarray(self._fused_rows, jnp.int32)
                      if self._fused is not None else None)
        return (self.params, self._table, self._tok, self._fused, fused_rows,
                jnp.asarray(self._active))

    def first_token_ready(self, rid: int) -> bool:
        """True once ``rid``'s first token exists (the TTFT marker: set at
        admission for monolithic prefill, at the final chunk when chunked)."""
        return rid in self._outputs

    @property
    def kv_table_bytes(self) -> int:
        """HBM held by the slot table's K/V payload (the capacity-vs-budget
        bench metric: dense = slots × max_seq rows; paged = the page pool).
        Excludes the int32 bookkeeping (pos / page map — KBs, not MBs)."""
        from repro.models.cache import tree_bytes

        return tree_bytes(self._table.layers)

    def kv_read_bytes_per_step(self) -> Dict[str, int]:
        """Analytic KV HBM bytes one decode step reads, at the engine's
        *current* occupancy (call it mid-flight).

        ``paged_kernel`` counts only the pages that hold live tokens — what
        the in-place kernel DMAs (Σ_active ceil((pos+1)/page_size) pages,
        also given as ``paged_pages_live``); ``paged_blocks_live`` counts the
        page blocks its per-slot loop runs over (Σ_active ceil(pages / P),
        P from ``kernels.paged_attention.block_pages``).
        ``dense_gather`` counts every slot's full row — what the
        ``dense_view()`` gather path reads no matter how little of each slot
        is live (slots × view_seq for paged-gather, slots × max_seq dense).
        k + v, summed over all stacked attention layer entries."""
        itemsize = jnp.dtype(self.cache_dtype).itemsize
        n_entries = sum(int(e["k"].shape[0]) for e in self._table.layers)
        row_bytes = 2 * self.cfg.num_kv_heads * self.cfg.resolved_head_dim \
            * itemsize * n_entries  # k+v bytes per cached token
        pos = np.asarray(self._table.pos)
        if self.paged:
            from repro.kernels.paged_attention import block_pages

            pg = self.page_size
            pool_shape = self._table.layers[0]["k"].shape[1:]
            P = block_pages(pool_shape, itemsize, self._table.pages_per_slot)
            live = -(-(pos[self._active] + 1) // pg)  # ceil: pages per slot
            pages = int(np.sum(live))
            view_seq = self._table.view_seq
            return {"paged_kernel": pages * pg * row_bytes,
                    "paged_pages_live": pages,
                    "paged_blocks_live": int(np.sum(-(-live // P))),
                    "dense_gather": self.max_slots * view_seq * row_bytes}
        return {"paged_kernel": 0,
                "dense_gather": self.max_slots * self.max_seq * row_bytes}
