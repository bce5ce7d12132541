"""Continuous-batching engine (launch/engine.py): slot lifecycle, mid-flight
joins, mixed standalone+C2C batches, and the one-trace compilation guarantee."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.configs.case_study import tiny_zoo
from repro.core import fuser as F
from repro.launch.engine import ContinuousBatchingEngine
from repro.models import transformer as T
from repro.models.cache import FusedPrefix, KVCache, PREFIX_MASK_BIAS

VOCAB = 64


@pytest.fixture(scope="module")
def cfg():
    return ModelConfig(name="eng-tiny", family="dense", num_layers=2,
                       d_model=32, num_heads=2, num_kv_heads=1, head_dim=16,
                       d_ff=64, vocab_size=VOCAB, tie_embeddings=True)


@pytest.fixture(scope="module")
def params(cfg):
    return T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)


def _prompt(key, n):
    return jax.random.randint(key, (1, n), 0, VOCAB)


def _solo(cfg, params, prompt, steps, max_seq, fused=None):
    """Reference greedy run on the plain (scalar-pos) decode path."""
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


# ------------------------------------------------------------- slot lifecycle


def test_slot_admission_eviction_reuse(cfg, params):
    """More requests than slots: slots are freed on completion and reused, and
    every request still matches its solo reference."""
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2, max_seq=48)
    key = jax.random.PRNGKey(1)
    reqs = [( _prompt(jax.random.fold_in(key, i), 4 + i), 3 + i)
            for i in range(5)]
    rids = [eng.submit(p, n) for p, n in reqs]
    assert eng.num_active == 0 and eng.num_queued == 5
    done = {c.rid: c.tokens for c in eng.drain()}
    assert eng.num_active == 0 and eng.num_queued == 0
    assert eng.stats["admitted"] == 5 and eng.stats["completed"] == 5
    for rid, (p, n) in zip(rids, reqs):
        assert np.array_equal(done[rid], _solo(cfg, params, p, n, 48))


def test_slot_insert_evict_roundtrip(cfg, params):
    """KVCache.insert_slot/evict_slot: inserted slot carries the request's
    position; evicted slot resets to 0 and hides its stale keys."""
    table = KVCache.init_slots(cfg, 3, 32, jnp.float32)
    p = _prompt(jax.random.PRNGKey(2), 6)
    _, req = T.prefill(cfg, params, p, max_seq=32, cache_dtype=jnp.float32)
    table = table.insert_slot(1, req, 6)
    assert table.pos.shape == (3,)
    assert table.pos.tolist() == [0, 6, 0]
    table = table.evict_slot(1)
    assert table.pos.tolist() == [0, 0, 0]


def test_completion_at_prefill_never_occupies_slot(cfg, params):
    """max_new_tokens=1 completes from the prefill logits directly."""
    eng = ContinuousBatchingEngine(cfg, params, max_slots=1, max_seq=32)
    p = _prompt(jax.random.PRNGKey(3), 5)
    rid = eng.submit(p, 1)
    done = {c.rid: c.tokens for c in eng.drain()}
    assert np.array_equal(done[rid], _solo(cfg, params, p, 1, 32))
    assert eng.stats["decode_steps"] == 0


# ------------------------------------------------------------ mid-flight joins


def test_midflight_join_matches_solo(cfg, params):
    """A request admitted while others are mid-decode produces exactly the
    tokens of a solo run (slot isolation)."""
    eng = ContinuousBatchingEngine(cfg, params, max_slots=3, max_seq=48)
    key = jax.random.PRNGKey(4)
    p1, p2, p3 = (_prompt(jax.random.fold_in(key, i), n)
                  for i, n in enumerate((7, 5, 9)))
    r1 = eng.submit(p1, 10)
    for _ in range(3):
        eng.step()
    r2 = eng.submit(p2, 6)   # joins while r1 is mid-decode
    r3 = eng.submit(p3, 8)
    done = {c.rid: c.tokens for c in eng.drain()}
    assert np.array_equal(done[r1], _solo(cfg, params, p1, 10, 48))
    assert np.array_equal(done[r2], _solo(cfg, params, p2, 6, 48))
    assert np.array_equal(done[r3], _solo(cfg, params, p3, 8, 48))


# ----------------------------------------------------------------- mixed batch


def _tiny_c2c():
    zoo = tiny_zoo(vocab_size=VOCAB)
    rx, tx = zoo["receiver"], zoo["transmitters"][0]
    key = jax.random.PRNGKey(5)
    p_rx = T.init_params(rx, key, jnp.float32)
    p_tx = T.init_params(tx, jax.random.fold_in(key, 1), jnp.float32)
    fz = F.init_fuser(tx, rx, jax.random.fold_in(key, 2))
    return rx, p_rx, tx, p_tx, fz


def test_mixed_standalone_c2c_batch():
    """Standalone and C2C-fused requests share one slot table; each matches
    its own solo reference, and the fixed-bucket prefix mask is exact."""
    rx, p_rx, tx, p_tx, fz = _tiny_c2c()
    key = jax.random.PRNGKey(6)
    pa = _prompt(key, 6)
    pb = _prompt(jax.random.fold_in(key, 1), 5)
    S = pa.shape[1]
    _, txc = T.prefill(tx, p_tx, pa, max_seq=S, cache_dtype=jnp.float32)
    fused = F.project_cache(fz, tx, rx, txc.export_stack(tx, length=S))

    eng = ContinuousBatchingEngine(rx, p_rx, max_slots=2, max_seq=40,
                                   max_prefix=8)
    ra = eng.submit(pa, 7, fused=fused)
    rb = eng.submit(pb, 7)
    done = {c.rid: c for c in eng.drain()}
    assert done[ra].protocol == "c2c" and done[rb].protocol == "standalone"
    # unpadded reference == engine (prefix padded to the bucket): mask exact
    assert np.array_equal(done[ra].tokens, _solo(rx, p_rx, pa, 7, 40, fused))
    assert np.array_equal(done[rb].tokens, _solo(rx, p_rx, pb, 7, 40))


def test_padded_prefix_mask_is_exact():
    """FusedPrefix.pad / FusedPrefix.empty: masked positions carry zero
    attention mass, so a padded prefix equals the unpadded one and an empty
    prefix equals no prefix."""
    rx, p_rx, tx, p_tx, fz = _tiny_c2c()
    p = _prompt(jax.random.PRNGKey(7), 6)
    _, txc = T.prefill(tx, p_tx, p, max_seq=6, cache_dtype=jnp.float32)
    fused = F.project_cache(fz, tx, rx, txc.export_stack(tx, length=6))
    padded = fused.pad(11)
    assert padded.k.shape[-2] == 11
    assert float(padded.bias[..., -1].max()) == float(
        jnp.float32(PREFIX_MASK_BIAS))
    assert np.array_equal(_solo(rx, p_rx, p, 5, 32, fused),
                          _solo(rx, p_rx, p, 5, 32, padded))
    empty = FusedPrefix.empty(rx, 1, 4, jnp.float32)
    assert np.array_equal(_solo(rx, p_rx, p, 5, 32),
                          _solo(rx, p_rx, p, 5, 32, empty))


# ------------------------------------------------------------ recompile count


def test_decode_jits_exactly_once_across_mixes():
    """The decode step traces once, no matter how the request mix changes
    (standalone-only -> fused-only -> mixed, different prefix lengths)."""
    _mixes_trace_once(jnp.float32)


def test_bf16_cache_traces_once_across_mixes():
    """With a bf16 cache a float32 fused prefix meets the cache-dtype
    all-masked prefix of standalone requests: the engine holds both at the
    cache dtype, so neither prefill nor decode retraces."""
    _mixes_trace_once(jnp.bfloat16)


def _mixes_trace_once(cache_dtype):
    rx, p_rx, tx, p_tx, fz = _tiny_c2c()
    key = jax.random.PRNGKey(8)
    eng = ContinuousBatchingEngine(rx, p_rx, max_slots=2, max_seq=40,
                                   max_prefix=8, prompt_bucket=8,
                                   cache_dtype=cache_dtype)

    def fused_for(p):
        S = p.shape[1]
        _, c = T.prefill(tx, p_tx, p, max_seq=S, cache_dtype=jnp.float32)
        return F.project_cache(fz, tx, rx, c.export_stack(tx, length=S))

    # wave 1: standalone only
    eng.submit(_prompt(key, 5), 4)
    eng.drain()
    # wave 2: fused only, prefix length 6
    p = _prompt(jax.random.fold_in(key, 1), 6)
    eng.submit(p, 4, fused=fused_for(p))
    eng.drain()
    # wave 3: mixed, different prefix length (3) and prompt lengths
    q = _prompt(jax.random.fold_in(key, 2), 3)
    eng.submit(q, 4, fused=fused_for(q))
    eng.submit(_prompt(jax.random.fold_in(key, 3), 7), 4)
    eng.drain()

    assert eng.stats["decode_steps"] > 0
    assert eng.stats["decode_traces"] == 1, (
        "decode step re-traced as the request mix changed")
    # bucketed prompts: one prefill trace covers every wave too
    assert eng.stats["prefill_traces"] == 1


# ------------------------------------------------------- fedrefine submit/drain


def test_fedrefine_submit_drain_mixed_protocols():
    """FedRefineSystem.submit()/drain(): standalone, C2C and T2T requests
    coexist in one engine; explicit protocols without transmitters raise."""
    from repro.core.fedrefine import FedRefineSystem, Participant

    zoo = tiny_zoo(vocab_size=VOCAB)
    key = jax.random.PRNGKey(10)
    members = [Participant(c.name, c, T.init_params(c, jax.random.fold_in(key, i),
                                                    jnp.float32))
               for i, c in enumerate([zoo["receiver"], zoo["transmitters"][0]])]
    system = FedRefineSystem.build(members)
    rx = members[0].name
    system.make_engine(rx, max_slots=3, max_seq=64, max_prefix=8)
    p = _prompt(key, 5)
    r_solo = system.submit(rx, p, 3, protocol="standalone")
    r_c2c = system.submit(rx, p, 3, protocol="c2c")
    r_t2t = system.submit(rx, p, 3, protocol="t2t")
    out = system.drain(rx)
    assert out[r_solo]["protocol"] == "standalone"
    assert out[r_c2c]["protocol"] == "c2c"
    assert out[r_t2t]["protocol"] == "t2t"
    assert all(len(out[r]["tokens"]) == 3 for r in (r_solo, r_c2c, r_t2t))
    assert out[r_c2c]["transmitters"] == [members[1].name]
    assert system.engines[rx].stats["decode_traces"] == 1

    # a receiver-only system cannot satisfy an explicit c2c request
    lone = FedRefineSystem.build(members[:1])
    lone.make_engine(rx, max_slots=1, max_seq=32, max_prefix=4)
    with pytest.raises(ValueError, match="no transmitter"):
        lone.submit(rx, p, 2, protocol="c2c")


# ----------------------------------------------------- other block families


@pytest.mark.parametrize("arch", ["recurrentgemma_9b", "mamba2_130m"])
def test_engine_stateful_families(arch):
    """Per-slot decode through swa ring buffers (RecurrentGemma) and
    recurrent/SSD states (Mamba-2): mid-flight joins still match solo runs.
    Stateful families use exact-length prefill (no prompt bucketing)."""
    from repro.configs.base import get_smoke_config

    cfg = get_smoke_config(arch)
    params = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2, max_seq=32,
                                   prompt_bucket=8)
    assert eng.prompt_bucket is None  # stateful: bucketing must be refused
    key = jax.random.PRNGKey(1)
    p1 = jax.random.randint(key, (1, 6), 0, cfg.vocab_size)
    p2 = jax.random.randint(jax.random.fold_in(key, 1), (1, 4), 0,
                            cfg.vocab_size)
    r1 = eng.submit(p1, 6)
    eng.step()
    r2 = eng.submit(p2, 4)  # joins mid-decode
    done = {c.rid: c.tokens for c in eng.drain()}
    assert np.array_equal(done[r1], _solo(cfg, params, p1, 6, 32))
    assert np.array_equal(done[r2], _solo(cfg, params, p2, 4, 32))
    assert eng.stats["decode_traces"] == 1


# ------------------------------------------------------------- per-slot decode


def test_per_slot_positions_decode_parity(cfg, params):
    """Vector-pos decode_step == scalar-pos decode_step when all slots happen
    to sit at the same position (the refactor preserves the lockstep path)."""
    B, S = 2, 6
    toks = jax.random.randint(jax.random.PRNGKey(9), (B, S + 1), 0, VOCAB)
    _, cache = T.prefill(cfg, params, toks[:, :S], max_seq=S + 2,
                         cache_dtype=jnp.float32)
    lg_scalar, _ = T.decode_step(cfg, params, cache, toks[:, S])
    vec_cache = cache.with_pos(jnp.full((B,), cache.pos, jnp.int32))
    lg_vec, new_cache = T.decode_step(cfg, params, vec_cache, toks[:, S])
    assert float(jnp.abs(lg_scalar - lg_vec).max()) < 1e-5
    assert new_cache.pos.tolist() == [S + 1] * B


# ------------------------------------------------------------- paged slots


def test_paged_engine_matches_dense_byte_identical(cfg, params):
    """Paged SlotTable decode == dense-slot decode, token for token: paging is
    a pure layout change (gather view + per-slot mask), never numerics."""
    key = jax.random.PRNGKey(20)
    reqs = [(_prompt(jax.random.fold_in(key, i), 4 + i), 3 + i)
            for i in range(5)]
    dense = ContinuousBatchingEngine(cfg, params, max_slots=2, max_seq=48)
    paged = ContinuousBatchingEngine(cfg, params, max_slots=2, max_seq=48,
                                     paged=True, page_size=8)
    rd = [dense.submit(p, n) for p, n in reqs]
    rp = [paged.submit(p, n) for p, n in reqs]
    out_d = {c.rid: c.tokens for c in dense.drain()}
    out_p = {c.rid: c.tokens for c in paged.drain()}
    for a, b in zip(rd, rp):
        assert np.array_equal(out_d[a], out_p[b])
    assert paged.stats["decode_traces"] == 1


def test_paged_capacity_beyond_dense_budget(cfg, params):
    """A paged pool sized for 2 dense slots serves 4 concurrent short
    requests (the ROADMAP paged-KV capacity win), and frees pages on
    completion."""
    max_seq, page = 32, 8
    dense_slots = 2
    eng = ContinuousBatchingEngine(
        cfg, params, max_slots=4, max_seq=max_seq, paged=True,
        page_size=page, num_pages=dense_slots * max_seq // page)
    key = jax.random.PRNGKey(21)
    prompts = [_prompt(jax.random.fold_in(key, i), 5) for i in range(4)]
    rids = [eng.submit(p, 6) for p in prompts]  # 11 tok -> 2 pages each
    done = {c.rid: c.tokens for c in eng.drain()}
    assert eng.stats["peak_active"] == 4  # 2x the dense-slot equivalent
    # every page is either free or pinned only by the radix prefix index;
    # dropping the index returns the pool to fully free
    eng._allocator.assert_consistent()
    assert eng._allocator.num_free + eng._radix.num_pages \
        == eng._table.num_pages
    eng._radix.clear(eng._allocator)
    assert eng._allocator.num_free == eng._table.num_pages
    for rid, p in zip(rids, prompts):
        assert np.array_equal(done[rid], _solo(cfg, params, p, 6, max_seq))


def test_paged_blocks_admission_until_pages_free(cfg, params):
    """When the pool is exhausted the head request waits (FIFO) and is
    admitted as soon as a completion returns pages."""
    eng = ContinuousBatchingEngine(cfg, params, max_slots=4, max_seq=32,
                                   paged=True, page_size=8, num_pages=4)
    key = jax.random.PRNGKey(22)
    p1, p2 = _prompt(key, 5), _prompt(jax.random.fold_in(key, 1), 5)
    r1 = eng.submit(p1, 6)   # 11 tok -> 2 pages
    r2 = eng.submit(p2, 10)  # 15 tok -> 2 pages
    eng.step()
    assert eng.num_active == 2 and eng.num_queued == 0
    r3 = eng.submit(p1, 3)   # pool full: must wait for r1/r2 to finish
    eng.step()
    assert eng.num_queued == 1
    done = {c.rid: c.tokens for c in eng.drain()}
    assert set(done) == {r1, r2, r3}
    assert np.array_equal(done[r3], _solo(cfg, params, p1, 3, 32))


def test_paged_kernel_path_matches_gather_path(cfg, params):
    """The in-place paged-attention kernel (default) and the dense_view()
    gather reference produce identical tokens; the kernel path never gathers
    a dense view during decode."""
    key = jax.random.PRNGKey(25)
    reqs = [(_prompt(jax.random.fold_in(key, i), 4 + i), 3 + i)
            for i in range(4)]
    mk = lambda mode: ContinuousBatchingEngine(
        cfg, params, max_slots=2, max_seq=48, paged=True, page_size=8,
        paged_attention=mode)
    kern, gath = mk("kernel"), mk("gather")
    rk = [kern.submit(p, n) for p, n in reqs]
    rg = [gath.submit(p, n) for p, n in reqs]
    out_k = {c.rid: c.tokens for c in kern.drain()}
    out_g = {c.rid: c.tokens for c in gath.drain()}
    for a, b in zip(rk, rg):
        assert np.array_equal(out_k[a], out_g[b])
    assert kern.stats["decode_view_gathers"] == 0
    assert gath.stats["decode_view_gathers"] == 1  # trace-time: once
    assert kern.stats["decode_traces"] == 1


def test_paged_kernel_with_fused_prefix_matches_dense():
    """C2C through the paged kernel: the fused prefix is LSE-merged from the
    kernel's online-softmax stats, and still matches the dense engine's
    concat-path tokens."""
    rx, p_rx, tx, p_tx, fz = _tiny_c2c()
    key = jax.random.PRNGKey(26)
    pa, pb = _prompt(key, 6), _prompt(jax.random.fold_in(key, 1), 5)
    _, txc = T.prefill(tx, p_tx, pa, max_seq=6, cache_dtype=jnp.float32)
    fused = F.project_cache(fz, tx, rx, txc.export_stack(tx, length=6))
    outs = {}
    for name, kw in (("dense", {}), ("paged", dict(paged=True, page_size=8))):
        eng = ContinuousBatchingEngine(rx, p_rx, max_slots=2, max_seq=40,
                                       max_prefix=8, **kw)
        ra = eng.submit(pa, 7, fused=fused)
        rb = eng.submit(pb, 7)
        done = {c.rid: c.tokens for c in eng.drain()}
        outs[name] = (done[ra], done[rb])
    assert np.array_equal(outs["dense"][0], outs["paged"][0])
    assert np.array_equal(outs["dense"][1], outs["paged"][1])


def test_paged_kernel_decode_step_direct(cfg, params):
    """transformer.decode_step dispatches on the SlotTable type: one step on a
    paged table == one step on its dense_view, and the new token lands on the
    right physical page (in-place write, no commit)."""
    from repro.models.cache import SlotTable

    table = SlotTable.init(cfg, 2, 32, jnp.float32, page_size=8)
    p = _prompt(jax.random.PRNGKey(27), 6)
    _, req = T.prefill(cfg, params, p, max_seq=32, cache_dtype=jnp.float32)
    pages = np.full((4,), table.invalid_page, np.int32)
    pages[:2] = [3, 1]  # out-of-order physical pages
    table = table.insert_slot(0, req, 6, jnp.asarray(pages))
    tok = jnp.array([7, 0], jnp.int32)
    lg_paged, new_table = T.decode_step(cfg, params, table, tok)
    lg_dense, _ = T.decode_step(cfg, params, table.dense_view(), tok)
    assert isinstance(new_table, SlotTable)
    assert jnp.argmax(lg_paged[0]) == jnp.argmax(lg_dense[0])
    assert float(jnp.abs(lg_paged[0] - lg_dense[0]).max()) < 1e-4
    # token at pos 6 -> page idx 0 -> physical page 3, offset 6
    e_new, e_old = new_table.layers[0], table.layers[0]
    assert float(jnp.abs(e_new["k"][:, 3, :, 6] - e_old["k"][:, 3, :, 6]).max()) > 0.0
    assert np.array_equal(new_table.page_map, table.page_map)
    assert new_table.pos.tolist() == [7, 1]


def test_kv_read_bytes_per_step_accounting(cfg, params):
    """The analytic HBM metric: in-place kernel bytes scale with live tokens,
    gather bytes with slots x view_seq (the engine_bench acceptance metric)."""
    eng = ContinuousBatchingEngine(cfg, params, max_slots=4, max_seq=32,
                                   paged=True, page_size=8)
    for i in range(4):
        eng.submit(_prompt(jax.random.fold_in(jax.random.PRNGKey(28), i), 5), 4)
    eng.step()  # all admitted, pos == 5 -> 1 page each
    b = eng.kv_read_bytes_per_step()
    n_entries = sum(int(e["k"].shape[0]) for e in eng._table.layers)
    row = 2 * cfg.num_kv_heads * cfg.resolved_head_dim * 4 * n_entries
    assert b["paged_kernel"] == 4 * 8 * row       # 4 slots x 1 live page
    assert b["dense_gather"] == 4 * 32 * row      # 4 slots x view_seq
    assert b["paged_kernel"] < b["dense_gather"]


def test_kv_read_bytes_per_step_counts_live_page_blocks(cfg, params):
    """The engagement counter: the paged kernel loops over
    ceil(ceil((pos+1)/page_size)/P) page blocks per active slot, with P as
    the kernel picks it from the pool's shape."""
    from repro.kernels.paged_attention import block_pages

    eng = ContinuousBatchingEngine(cfg, params, max_slots=4, max_seq=64,
                                   paged=True, page_size=8)
    for i, n in enumerate((5, 13, 20)):
        eng.submit(_prompt(jax.random.fold_in(jax.random.PRNGKey(29), i), n),
                   6)
    pool = eng._table.layers[0]["k"]
    P = block_pages(pool.shape[1:], pool.dtype.itemsize,
                    eng._table.pages_per_slot)
    for _ in range(3):
        eng.step()
        b = eng.kv_read_bytes_per_step()
        pages = -(-(np.asarray(eng._table.pos)[eng._active] + 1) // 8)
        assert b["paged_pages_live"] == int(pages.sum()) > 0
        assert b["paged_blocks_live"] == int((-(-pages // P)).sum())
        assert b["paged_kernel"] == b["paged_pages_live"] * 8 * (
            b["dense_gather"] // (4 * 64))


def test_paged_requires_pure_attention():
    from repro.configs.base import get_smoke_config

    cfg = get_smoke_config("recurrentgemma_9b")
    params = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    with pytest.raises(ValueError, match="pure full-attention"):
        ContinuousBatchingEngine(cfg, params, max_slots=2, max_seq=32,
                                 paged=True, page_size=8)


# -------------------------------------------------------- batch admission


def test_batch_admission_matches_solo_and_traces_once(cfg, params):
    """admit_batch>1 prefills same-bucket requests together: outputs equal
    solo runs and the prefill still traces once per bucket length."""
    eng = ContinuousBatchingEngine(cfg, params, max_slots=4, max_seq=48,
                                   admit_batch=4, prompt_bucket=8)
    key = jax.random.PRNGKey(23)
    reqs = [(_prompt(jax.random.fold_in(key, i), 3 + i), 4 + i)
            for i in range(4)]  # lengths 3..6 share the 8-bucket
    rids = [eng.submit(p, n) for p, n in reqs]
    done = {c.rid: c.tokens for c in eng.drain()}
    for rid, (p, n) in zip(rids, reqs):
        assert np.array_equal(done[rid], _solo(cfg, params, p, n, 48))
    assert eng.stats["prefill_traces"] == 1
    assert eng.stats["admit_batches"] == 1  # one forward admitted all four
    assert eng.stats["decode_traces"] == 1


def test_batch_admission_mixed_protocols():
    """Batched admission keeps per-request fused prefixes separated: a C2C
    and a standalone request admitted in one prefill each match their solo
    references."""
    rx, p_rx, tx, p_tx, fz = _tiny_c2c()
    key = jax.random.PRNGKey(24)
    pa = _prompt(key, 6)
    pb = _prompt(jax.random.fold_in(key, 1), 6)
    _, txc = T.prefill(tx, p_tx, pa, max_seq=6, cache_dtype=jnp.float32)
    fused = F.project_cache(fz, tx, rx, txc.export_stack(tx, length=6))
    eng = ContinuousBatchingEngine(rx, p_rx, max_slots=2, max_seq=40,
                                   max_prefix=8, admit_batch=2)
    ra = eng.submit(pa, 7, fused=fused)
    rb = eng.submit(pb, 7)
    done = {c.rid: c for c in eng.drain()}
    assert eng.stats["admit_batches"] == 1
    assert np.array_equal(done[ra].tokens, _solo(rx, p_rx, pa, 7, 40, fused))
    assert np.array_equal(done[rb].tokens, _solo(rx, p_rx, pb, 7, 40))


def test_paged_rejects_never_admittable_request(cfg, params):
    """A request whose page demand exceeds the whole pool fails at submit()
    instead of hanging drain() forever."""
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2, max_seq=32,
                                   paged=True, page_size=8, num_pages=2)
    with pytest.raises(ValueError, match="could never be admitted"):
        eng.submit(_prompt(jax.random.PRNGKey(30), 12), 10)  # 3 pages > 2


def test_paged_pages_sized_by_request_not_bucket(cfg, params):
    """Bucket padding must not inflate page reservations: a 5+3-token request
    under a large prompt bucket takes ceil(8/8)=1 page, not bucket/page."""
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2, max_seq=32,
                                   paged=True, page_size=8, num_pages=4,
                                   prompt_bucket=32)
    p = _prompt(jax.random.PRNGKey(31), 5)
    rid = eng.submit(p, 3)
    eng.step()
    assert eng._leases[0].num_pages == 1  # one page, despite the 32-bucket
    done = {c.rid: c.tokens for c in eng.drain()}
    assert np.array_equal(done[rid], _solo(cfg, params, p, 3, 32))


# -------------------------------------------------------- chunked prefill


def test_chunked_prefill_matches_monolithic(cfg, params):
    """Token-budget chunked prefill is a pure scheduling change: byte-identical
    tokens vs the monolithic paged engine on a mixed long/short workload
    (including a 1-token request), ONE chunk-prefill trace regardless of
    prompt lengths or chunk counts, and a clean sanitizer."""
    key = jax.random.PRNGKey(40)
    reqs = [(_prompt(jax.random.fold_in(key, i), n), m)
            for i, (n, m) in enumerate(
                [(37, 6), (5, 4), (21, 1), (12, 8), (40, 3)])]

    def run(**kw):
        eng = ContinuousBatchingEngine(cfg, params, max_slots=3, max_seq=64,
                                       paged=True, page_size=8, num_pages=40,
                                       sanitize=True, **kw)
        rids = [eng.submit(p, n) for p, n in reqs]
        done = {c.rid: c.tokens for c in eng.drain()}
        assert eng.sanitizer_report() == []
        return eng, [done[r] for r in rids]

    _, base = run()
    for budget in (4, 16):
        eng, out = run(prefill_token_budget=budget)
        for a, b in zip(base, out):
            assert np.array_equal(a, b)
        assert eng.stats["prefill_traces"] == 1
        assert eng.stats["decode_traces"] == 1
        assert eng.stats["prefill_chunks"] >= sum(
            -(-p.shape[1] // budget) for p, _ in reqs)


def test_chunked_prefill_no_decode_before_final_chunk(cfg, params):
    """Mid-prefill a slot is invisible to decode: no decode step runs (and the
    slot never activates) until the prompt's final chunk adopts its pages."""
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2, max_seq=64,
                                   paged=True, page_size=8,
                                   prefill_token_budget=8, sanitize=True)
    p = _prompt(jax.random.PRNGKey(41), 29)  # ceil(29/8) = 4 chunks
    rid = eng.submit(p, 5)
    for i in range(3):  # chunks 1..3: 24 of 29 tokens resident
        eng.step()
        assert eng.num_active == 0 and eng.stats["decode_steps"] == 0
        assert len(eng._partials) == 1
        assert eng._partials[0].done == 8 * (i + 1)
        # the reserved slot's device page row stays INVALID throughout
        assert (np.asarray(eng._table.page_map[eng._partials[0].slot])
                == eng._table.invalid_page).all()
    done = {c.rid: c.tokens for c in eng.drain()}
    assert eng.stats["prefill_chunks"] == 4
    assert np.array_equal(done[rid], _solo(cfg, params, p, 5, 64))


def test_chunked_prefill_radix_sharing_and_cow(cfg, params):
    """Radix hits still share pages under chunking: a common (non-page-
    aligned) system prompt is served from cached pages with a CoW copy of the
    partial page, only the tail is chunked, and tokens stay byte-identical
    to the monolithic engine's."""
    key = jax.random.PRNGKey(42)
    sys_p = _prompt(jax.random.fold_in(key, 100), 19)  # 19 % 8 != 0 -> CoW
    reqs = [(jnp.concatenate(
        [sys_p, _prompt(jax.random.fold_in(key, i), 6 + 3 * i)], 1), 4 + i)
        for i in range(3)]

    def run(**kw):
        eng = ContinuousBatchingEngine(cfg, params, max_slots=2, max_seq=64,
                                       paged=True, page_size=8, num_pages=40,
                                       sanitize=True, **kw)
        rids = [eng.submit(p, n) for p, n in reqs]
        done = {c.rid: c.tokens for c in eng.drain()}
        assert eng.sanitizer_report() == []
        return eng, [done[r] for r in rids]

    _, base = run()
    eng, out = run(prefill_token_budget=8)
    for a, b in zip(base, out):
        assert np.array_equal(a, b)
    assert eng.stats["radix_hits"] == 2
    assert eng.stats["cow_copies"] == 2
    assert eng.stats["radix_matched_tokens"] > 0
    # shared tokens never re-prefilled: chunked tokens cover only the tails
    total = sum(p.shape[1] for p, _ in reqs)
    assert eng.stats["prefill_tokens"] == \
        total - eng.stats["radix_matched_tokens"]


def test_chunked_prefill_validation(cfg, params):
    with pytest.raises(ValueError, match="needs paged=True"):
        ContinuousBatchingEngine(cfg, params, max_slots=2, max_seq=32,
                                 prefill_token_budget=8)
    with pytest.raises(ValueError, match="must be >= 1"):
        ContinuousBatchingEngine(cfg, params, max_slots=2, max_seq=32,
                                 paged=True, page_size=8,
                                 prefill_token_budget=0)


# ------------------------------------------- prompt bucket max_seq headroom


def test_prompt_of_max_seq_rejected_with_headroom_error(cfg, params):
    """Regression: a prompt of exactly max_seq must be rejected up front with
    an error naming the missing decode headroom — bucket rounding clamps at
    max_seq, so such a prompt would otherwise land in a bucket with zero room
    for the first decoded token."""
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2, max_seq=32,
                                   prompt_bucket=8)
    with pytest.raises(ValueError, match="no headroom"):
        eng.submit(_prompt(jax.random.PRNGKey(43), 32), 1)
    with pytest.raises(ValueError, match="no headroom"):
        eng._bucket_len(32)  # the guard also covers direct callers
    # the boundary that IS admissible: prompt + gen == max_seq exactly, with
    # the bucket rounding the prompt up to the max_seq clamp
    p = _prompt(jax.random.PRNGKey(44), 31)
    rid = eng.submit(p, 1)
    done = {c.rid: c.tokens for c in eng.drain()}
    assert np.array_equal(done[rid], _solo(cfg, params, p, 1, 32))
