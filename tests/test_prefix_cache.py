"""PageAllocator refcounting, radix prefix index, and the engine's
copy-on-write page-sharing admission path (launch/prefix_cache.py +
models/cache.PageAllocator).

Sharing invariants pinned here (the ISSUE's satellite list):
- refcounts never go negative (double-free / free-page sharing raise),
- CoW divergence decodes byte-identical to an unshared run,
- radix lookup returns the longest matching prefix (brute-force oracle),
- evicting one sharer never frees pages another slot still maps,
- a fused C2C prefix is inserted once per digest and reused by every
  subsequent request fusing the same digest.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.configs.case_study import tiny_zoo
from repro.core import fuser as F
from repro.launch.engine import ContinuousBatchingEngine
from repro.launch.prefix_cache import RadixPrefixIndex
from repro.models import transformer as T
from repro.models.cache import (FusedPrefix, KVCache, KVStack, PageAllocator,
                                PageLease, SlotTable)

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # hermetic fallback (see repro/testing/propcheck.py)
    from repro.testing.propcheck import given, settings, strategies as st

VOCAB = 64


@pytest.fixture(scope="module")
def cfg():
    return ModelConfig(name="pfx-tiny", family="dense", num_layers=2,
                       d_model=32, num_heads=2, num_kv_heads=1, head_dim=16,
                       d_ff=64, vocab_size=VOCAB, tie_embeddings=True)


@pytest.fixture(scope="module")
def params(cfg):
    return T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)


def _prompt(key, n):
    return jax.random.randint(key, (1, n), 0, VOCAB)


def _solo(cfg, params, prompt, steps, max_seq, fused=None):
    ek = (FusedPrefix.ensure(fused).to_extra_kv(cfg)
          if fused is not None else None)
    logits, cache = T.prefill(cfg, params, prompt, max_seq=max_seq,
                              cache_dtype=jnp.float32, extra_kv=ek)
    tok = jnp.argmax(logits[:, prompt.shape[1] - 1], -1)
    out = [tok]
    for _ in range(steps - 1):
        lg, cache = T.decode_step(cfg, params, cache, tok, extra_kv=ek)
        tok = jnp.argmax(lg, -1)
        out.append(tok)
    return np.asarray(jnp.stack(out, 1)[0])


# ------------------------------------------------------------ PageAllocator


def test_allocator_alloc_release_roundtrip():
    a = PageAllocator(8)
    ids = a.alloc(3)
    assert len(set(ids)) == 3 and a.num_free == 5
    assert all(a.refcount(p) == 1 for p in ids)
    a.release(ids)
    assert a.num_free == 8
    a.assert_consistent()


def test_allocator_share_keeps_pages_alive():
    """Evicting one sharer never frees a page the other still holds."""
    a = PageAllocator(4)
    lease1 = a.lease(fresh=2)
    lease2 = a.lease(shared=lease1.ids(), fresh=1)
    assert a.refcount(lease1.ids()[0]) == 2
    a.release(lease1)  # sharer 1 evicted
    assert a.num_free == 1  # shared pages survive, only nothing was exclusive
    assert all(a.refcount(p) == 1 for p in lease2.ids())
    a.release(lease2)
    assert a.num_free == 4
    a.assert_consistent()


def test_allocator_refcount_underflow_raises():
    a = PageAllocator(2)
    ids = a.alloc(1)
    a.release(ids)
    with pytest.raises(ValueError, match="underflow"):
        a.release(ids)  # double free
    with pytest.raises(ValueError, match="free page"):
        a.share(ids)  # sharing a freed page
    a.assert_consistent()


def test_allocator_exhaustion_raises():
    a = PageAllocator(2)
    assert a.can_alloc(2) and not a.can_alloc(3)
    with pytest.raises(RuntimeError, match="exhausted"):
        a.alloc(3)
    a.alloc(2)
    with pytest.raises(RuntimeError, match="exhausted"):
        a.lease(fresh=1)


def test_allocator_cow_swaps_shared_for_owned():
    a = PageAllocator(4)
    owner = a.lease(fresh=1)
    sharer = a.lease(shared=owner.ids())
    assert not sharer.owned[0]
    with pytest.raises(ValueError, match="already owned"):
        a.cow(owner, 0)
    src, dst = a.cow(sharer, 0)
    assert src == owner.ids()[0] and dst != src
    assert sharer.owned[0] and sharer.ids() == [dst]
    assert a.refcount(src) == 1 and a.refcount(dst) == 1
    a.release(owner)
    a.release(sharer)
    assert a.num_free == 4
    a.assert_consistent()


@settings(max_examples=40)
@given(st.lists(st.tuples(st.integers(0, 2), st.integers(0, 3)),
                min_size=0, max_size=40))
def test_allocator_refcounts_never_negative(ops):
    """Random op soup (alloc/share/release/cow) through the public API keeps
    the allocator consistent: counts never negative, free list exact."""
    a = PageAllocator(6)
    held = []  # leases we still hold
    for op, arg in ops:
        if op == 0 and a.can_alloc(1):  # fresh lease
            held.append(a.lease(fresh=1))
        elif op == 1 and held:  # share an existing lease's pages
            src = held[arg % len(held)]
            held.append(a.lease(shared=src.ids()))
        elif op == 2 and held:  # release one
            a.release(held.pop(arg % len(held)))
        elif op == 3 and held and a.can_alloc(1):  # CoW any shared page
            lease = held[arg % len(held)]
            shared_idx = [i for i in range(lease.num_pages)
                          if not lease.owned[i]]
            if shared_idx:
                a.cow(lease, shared_idx[0])
        a.assert_consistent()
        assert a.pages_in_use + a.num_free == 6
    for lease in held:
        a.release(lease)
    assert a.num_free == 6
    a.assert_consistent()


def test_page_lease_row_padding():
    lease = PageLease(page_ids=np.asarray([5, 2], np.int32),
                      owned=np.asarray([False, True]))
    row = lease.page_row(4, invalid=9)
    assert row.tolist() == [5, 2, 9, 9]
    with pytest.raises(ValueError, match="exceeds"):
        lease.page_row(1, invalid=9)


# ------------------------------------------------------- RadixPrefixIndex


def _register_seq(idx, alloc, tokens, digest=None):
    pg = idx.page_size
    n = -(-len(tokens) // pg)  # ceil: full pages + the partial tail page
    ids = alloc.alloc(n)
    idx.register(digest, np.asarray(tokens), ids, alloc)
    return ids


@settings(max_examples=60)
@given(st.lists(st.lists(st.integers(0, 1), min_size=1, max_size=12),
                min_size=0, max_size=5),
       st.lists(st.integers(0, 1), min_size=1, max_size=12))
def test_radix_longest_match_oracle(seqs, query):
    """lookup() returns exactly min(max lcp over registered sequences,
    len(query) - 1) matched tokens — the longest-matching-prefix contract."""
    alloc = PageAllocator(256)
    idx = RadixPrefixIndex(3, max_partials_per_node=32)
    for s in seqs:
        _register_seq(idx, alloc, s)
    m = idx.lookup(None, np.asarray(query))
    expect = min(max((_lcp(s, query) for s in seqs), default=0),
                 len(query) - 1)
    got = 0 if m is None else m.matched
    assert got == expect, (seqs, query, got, expect)
    if m is not None:
        # full pages + partial arithmetic is internally consistent
        assert m.matched == len(m.page_ids) * 3 + m.partial_tokens
        assert m.partial_tokens < 3
        # the slot's leases release fine and the index pins stay consistent
        alloc.assert_consistent()


def _lcp(a, b):
    n = min(len(a), len(b))
    for i in range(n):
        if a[i] != b[i]:
            return i
    return n


def test_radix_keyed_by_fused_digest():
    """Pages registered under one fused digest are invisible to lookups under
    another (prompt KV depends on the attended fused prefix)."""
    alloc = PageAllocator(16)
    idx = RadixPrefixIndex(2)
    toks = [1, 2, 3, 4, 5]
    _register_seq(idx, alloc, toks, digest="aaa")
    assert idx.lookup("aaa", np.asarray(toks)).matched == 4  # capped at S-1
    assert idx.lookup("bbb", np.asarray(toks)) is None
    assert idx.lookup(None, np.asarray(toks)) is None


def test_radix_evict_frees_only_unshared():
    """Index eviction releases pins LRU-first, but a page a live lease still
    maps survives (refcount protects it)."""
    alloc = PageAllocator(16)
    idx = RadixPrefixIndex(2)
    ids_a = _register_seq(idx, alloc, [1, 2, 3, 4])  # 2 full nodes
    alloc.release(ids_a)  # registering slot evicted; index pins remain
    lease = alloc.lease(shared=[ids_a[0]])  # a new slot maps page 0
    freed = idx.evict(alloc, want_pages=2)
    # page ids_a[1] freed; ids_a[0] survives its pin release via the lease
    assert freed == 1
    assert alloc.refcount(ids_a[0]) == 1
    assert alloc.refcount(ids_a[1]) == 0
    alloc.release(lease)
    assert alloc.num_free == 16
    alloc.assert_consistent()


def test_radix_clear_releases_all_pins():
    alloc = PageAllocator(16)
    idx = RadixPrefixIndex(2)
    ids1 = _register_seq(idx, alloc, [1, 2, 3, 4, 5], digest="d")
    ids2 = _register_seq(idx, alloc, [1, 2, 9], digest="d")
    alloc.release(ids1)  # both registering slots evicted; pins remain
    alloc.release(ids2)
    # 2 full nodes + 2 partials survive; ids2[0] (duplicate chunk) was freed
    assert alloc.pages_in_use == idx.num_pages == 4
    idx.clear(alloc)
    assert alloc.num_free == 16
    alloc.assert_consistent()


# ------------------------------------------- engine sharing + CoW identity


def _shared_prompts(key, n, shared_len, tail_len):
    """n prompts of one shared prefix plus a random tail each. Tail i starts
    with token i, so no two tails share a first token and every radix match
    is exactly the ``shared_len`` shared tokens, whatever the random draw."""
    assert n <= VOCAB
    shared = jax.random.randint(jax.random.fold_in(key, 99),
                                (1, shared_len), 0, VOCAB, jnp.int32)
    out = []
    for i in range(n):
        tail = jax.random.randint(jax.random.fold_in(key, i),
                                  (1, tail_len), 0, VOCAB, jnp.int32)
        out.append(jnp.concatenate([shared, tail.at[0, 0].set(i)], axis=1))
    return out


def test_engine_shared_prefix_byte_identical(cfg, params):
    """Shared-system-prompt workload: the prefix cache shares pages, CoW
    copies the partially-matched page, prefills only suffixes — and decodes
    byte-identically to the unshared engine."""
    prompts = _shared_prompts(jax.random.PRNGKey(40), 6, 20, 6)  # S=26

    def run(pc):
        eng = ContinuousBatchingEngine(cfg, params, max_slots=8, max_seq=64,
                                       paged=True, page_size=8, num_pages=32,
                                       prefix_cache=pc)
        rids = [eng.submit(p, 6) for p in prompts]
        done = {c.rid: c.tokens for c in eng.drain()}
        return [done[r] for r in rids], eng

    out_on, eng = run(True)
    out_off, _ = run(False)
    for a, b in zip(out_on, out_off):
        assert np.array_equal(a, b)
    st = eng.stats
    assert st["radix_hits"] == 5 and st["shared_admits"] == 5
    assert st["cow_copies"] >= 1  # 20 % 8 != 0: partial page CoW-copied
    assert st["radix_matched_tokens"] == 5 * 20
    assert st["decode_traces"] == 1 and st["suffix_prefill_traces"] == 1
    # engine holds no raw page-id lists: the allocator is the only authority
    assert not hasattr(eng, "_free_pages") and not hasattr(eng, "_slot_pages")
    eng._allocator.assert_consistent()
    assert not eng._leases  # all released on completion
    assert eng._allocator.num_free + eng._radix.num_pages \
        == eng._table.num_pages


def test_engine_shared_prefix_fewer_prefill_tokens(cfg, params):
    """The capacity win: shared admissions prefill only suffixes."""
    prompts = _shared_prompts(jax.random.PRNGKey(41), 5, 24, 8)  # S=32

    def tokens_prefilled(pc):
        eng = ContinuousBatchingEngine(cfg, params, max_slots=8, max_seq=64,
                                       paged=True, page_size=8,
                                       prefix_cache=pc)
        for p in prompts:
            eng.submit(p, 4)
        eng.drain()
        return eng.stats["prefill_tokens"]

    on, off = tokens_prefilled(True), tokens_prefilled(False)
    assert off == 5 * 32
    assert on == 32 + 4 * (32 - 24)  # one full prefill + 4 suffixes
    assert on * 2 < off


def test_engine_sharer_eviction_leaves_other_decoding(cfg, params):
    """A short sharer finishing (and releasing its lease) must not disturb a
    long sharer still decoding from the same physical prefix pages."""
    pa, pb = _shared_prompts(jax.random.PRNGKey(42), 2, 16, 4)  # S=20
    eng = ContinuousBatchingEngine(cfg, params, max_slots=4, max_seq=64,
                                   paged=True, page_size=8, num_pages=16)
    ra = eng.submit(pa, 3)   # finishes early, releases shared pages
    rb = eng.submit(pb, 12)  # keeps decoding long after
    done = {c.rid: c.tokens for c in eng.drain()}
    assert eng.stats["shared_admits"] == 1
    assert np.array_equal(done[ra], _solo(cfg, params, pa, 3, 64))
    assert np.array_equal(done[rb], _solo(cfg, params, pb, 12, 64))
    eng._allocator.assert_consistent()


def test_engine_prefix_survives_sharer_completion(cfg, params):
    """Index pins outlive the registering request: a request submitted after
    the original owner completed still shares its pages."""
    pa, pb = _shared_prompts(jax.random.PRNGKey(43), 2, 16, 4)
    eng = ContinuousBatchingEngine(cfg, params, max_slots=4, max_seq=64,
                                   paged=True, page_size=8, num_pages=16)
    ra = eng.submit(pa, 3)
    done_a = {c.rid: c.tokens for c in eng.drain()}
    rb = eng.submit(pb, 5)  # owner long gone; pages live via index pins
    done_b = {c.rid: c.tokens for c in eng.drain()}
    assert eng.stats["radix_hits"] == 1
    assert np.array_equal(done_a[ra], _solo(cfg, params, pa, 3, 64))
    assert np.array_equal(done_b[rb], _solo(cfg, params, pb, 5, 64))


def test_engine_pool_pressure_evicts_index_not_slots(cfg, params):
    """When index pins would starve a fresh admission, LRU prefix entries are
    evicted to free pages; the engine never deadlocks on its own cache."""
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2, max_seq=32,
                                   paged=True, page_size=8, num_pages=4)
    key = jax.random.PRNGKey(44)
    # sequential: each leaves its pages pinned by the index after completion
    outs = {}
    for i in range(4):
        p = _prompt(jax.random.fold_in(key, i), 10)  # 2 pages each
        rid = eng.submit(p, 4)
        outs[rid] = (p, {c.rid: c.tokens for c in eng.drain()}[rid])
    for rid, (p, toks) in outs.items():
        assert np.array_equal(toks, _solo(cfg, params, p, 4, 32))
    eng._allocator.assert_consistent()


def test_engine_fused_digest_inserted_once():
    """A fused C2C prefix transmitted once is inserted into the row table
    once; every later request with the same digest reuses the row, and
    outputs match the non-shared engine."""
    zoo = tiny_zoo(vocab_size=VOCAB)
    rx, tx = zoo["receiver"], zoo["transmitters"][0]
    key = jax.random.PRNGKey(45)
    p_rx = T.init_params(rx, key, jnp.float32)
    p_tx = T.init_params(tx, jax.random.fold_in(key, 1), jnp.float32)
    fz = F.init_fuser(tx, rx, jax.random.fold_in(key, 2))
    src = _prompt(key, 6)
    _, txc = T.prefill(tx, p_tx, src, max_seq=6, cache_dtype=jnp.float32)
    fused = F.project_cache(fz, tx, rx, txc.export_stack(tx, length=6))
    prompts = [_prompt(jax.random.fold_in(key, 10 + i), 5 + i)
               for i in range(4)]

    def run(pc):
        eng = ContinuousBatchingEngine(rx, p_rx, max_slots=4, max_seq=40,
                                       max_prefix=8, paged=True, page_size=8,
                                       prefix_cache=pc)
        rids = [eng.submit(p, 5, fused=fused) for p in prompts]
        done = {c.rid: c.tokens for c in eng.drain()}
        return [done[r] for r in rids], eng.stats

    out_on, st = run(True)
    out_off, st_off = run(False)
    for a, b in zip(out_on, out_off):
        assert np.array_equal(a, b)
    assert st["fused_inserts"] == 1
    assert st["fused_digest_hits"] == len(prompts) - 1
    # row sharing is digest-level, independent of the radix prefix cache
    assert st_off["fused_inserts"] == 1
    for p, toks in zip(prompts, out_on):
        assert np.array_equal(toks, _solo(rx, p_rx, p, 5, 40, fused))


def test_engine_fused_row_reused_across_slot_turnover():
    """Slot reuse doesn't re-insert a known digest: rows are refcounted and
    the digest pin keeps the row warm between occupants."""
    zoo = tiny_zoo(vocab_size=VOCAB)
    rx, tx = zoo["receiver"], zoo["transmitters"][0]
    key = jax.random.PRNGKey(46)
    p_rx = T.init_params(rx, key, jnp.float32)
    p_tx = T.init_params(tx, jax.random.fold_in(key, 1), jnp.float32)
    fz = F.init_fuser(tx, rx, jax.random.fold_in(key, 2))
    src = _prompt(key, 6)
    _, txc = T.prefill(tx, p_tx, src, max_seq=6, cache_dtype=jnp.float32)
    fused = F.project_cache(fz, tx, rx, txc.export_stack(tx, length=6))
    eng = ContinuousBatchingEngine(rx, p_rx, max_slots=1, max_seq=40,
                                   max_prefix=8)
    for i in range(3):  # sequential: the single slot turns over each time
        p = _prompt(jax.random.fold_in(key, 20 + i), 5)
        rid = eng.submit(p, 4, fused=fused)
        done = {c.rid: c.tokens for c in eng.drain()}
        assert np.array_equal(done[rid], _solo(rx, p_rx, p, 4, 40, fused))
    assert eng.stats["fused_inserts"] == 1
    assert eng.stats["fused_digest_hits"] == 2


# ------------------------------------------------ unified insert_slot API


def test_insert_slot_polymorphic_over_lease(cfg, params):
    """KVCache.insert_slot accepts (and ignores) a PageLease in the same
    positional slot where SlotTable.insert_slot requires one."""
    p = _prompt(jax.random.PRNGKey(47), 6)
    _, req = T.prefill(cfg, params, p, max_seq=32, cache_dtype=jnp.float32)
    lease = PageLease(page_ids=np.asarray([0], np.int32),
                      owned=np.asarray([True]))

    dense = KVCache.init_slots(cfg, 2, 32, jnp.float32)
    with_lease = dense.insert_slot(0, req, 6, lease)
    without = dense.insert_slot(0, req, 6)
    assert jax.tree.all(jax.tree.map(
        lambda a, b: jnp.array_equal(a, b), with_lease, without))

    table = SlotTable.init(cfg, 2, 32, jnp.float32, page_size=8)
    via_lease = table.insert_slot(0, req, 6, lease)
    row = lease.page_row(table.pages_per_slot, table.invalid_page)
    via_row = table.insert_slot(0, req, 6, jnp.asarray(row))
    assert jax.tree.all(jax.tree.map(
        lambda a, b: jnp.array_equal(a, b), via_lease.layers, via_row.layers))
    assert np.array_equal(via_lease.page_map, via_row.page_map)


# ---------------------------------------------------- legacy dict interop


def test_getitem_emits_deprecation_warning(cfg):
    stack = KVStack(k=jnp.zeros((1, 1, 1, 2, 4)), v=jnp.zeros((1, 1, 1, 2, 4)))
    with pytest.warns(DeprecationWarning, match="dict-style access"):
        _ = stack["k"]
    fused = FusedPrefix.empty(cfg, 1, 4)
    with pytest.warns(DeprecationWarning, match="dict-style access"):
        _ = fused["bias"]
    cache = KVCache.init(cfg, 1, 8, jnp.float32)
    with pytest.warns(DeprecationWarning, match="dict-style access"):
        _ = cache["pos"]
    # attribute access stays silent and returns the same leaves
    assert stack.k is not None and fused.bias is not None
    assert cache.pos.shape == ()
