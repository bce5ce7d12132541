"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


def _mlp_params(k, d_in, d_h, d_out, dt):
    ks = jax.random.split(k, 6)
    mk = lambda kk, shape: (jax.random.normal(kk, shape, jnp.float32) * 0.05).astype(dt)
    return {
        "w1": {"w": mk(ks[0], (d_in, d_h)), "b": mk(ks[1], (d_h,))},
        "w2": {"w": mk(ks[2], (d_h, d_h)), "b": mk(ks[3], (d_h,))},
        "w3": {"w": mk(ks[4], (d_h, d_out)), "b": mk(ks[5], (d_out,))},
    }


@pytest.mark.parametrize("T,d_in,d_h,d_out", [
    (64, 96, 128, 160), (256, 128, 128, 128), (100, 48, 64, 80), (8, 32, 32, 32),
])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_fuser_mlp_sweep(T, d_in, d_h, d_out, dt):
    x = jax.random.normal(KEY, (T, d_in), jnp.float32).astype(dt)
    p = _mlp_params(KEY, d_in, d_h, d_out, dt)
    y = ops.fuser_mlp(p, x)
    yr = ref.fuser_mlp_ref(x, p["w1"]["w"], p["w1"]["b"], p["w2"]["w"],
                           p["w2"]["b"], p["w3"]["w"], p["w3"]["b"])
    tol = 1e-5 if dt == jnp.float32 else 5e-2
    assert y.shape == (T, d_out)
    assert float(jnp.abs(y.astype(jnp.float32) - yr.astype(jnp.float32)).max()) < tol


def test_fuser_mlp_batched_leading_dims():
    p = _mlp_params(KEY, 32, 48, 40, jnp.float32)
    x = jax.random.normal(KEY, (3, 5, 7, 32), jnp.float32)
    y = ops.fuser_mlp(p, x)
    assert y.shape == (3, 5, 7, 40)


@pytest.mark.parametrize("n,B,H,S,hd", [(3, 2, 2, 64, 32), (1, 1, 4, 128, 16),
                                        (5, 2, 1, 96, 64)])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_gated_fusion_sweep(n, B, H, S, hd, dt):
    ks = jax.random.split(KEY, 5)
    args = [jax.random.normal(k, (n, B, H, S, hd), jnp.float32).astype(dt)
            for k in ks[:4]]
    gate = jax.random.normal(ks[4], (n,))
    k1, v1 = ops.gated_fusion(*args, gate)
    k2, v2 = ref.gated_fusion_ref(*args, gate)
    tol = 1e-6 if dt == jnp.float32 else 2e-2
    assert float(jnp.abs((k1 - k2).astype(jnp.float32)).max()) < tol
    assert float(jnp.abs((v1 - v2).astype(jnp.float32)).max()) < tol


@pytest.mark.parametrize("B,H,Hkv,S,hd", [
    (2, 8, 2, 256, 64), (1, 4, 4, 128, 32),
    pytest.param(2, 16, 1, 512, 128, marks=pytest.mark.slow),  # largest interp case
    (1, 8, 8, 96, 64),
])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(B, H, Hkv, S, hd, dt):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32).astype(dt)
    k = jax.random.normal(ks[1], (B, Hkv, S, hd), jnp.float32).astype(dt)
    v = jax.random.normal(ks[2], (B, Hkv, S, hd), jnp.float32).astype(dt)
    bias = jnp.where(jax.random.uniform(ks[3], (B, S)) < 0.25, -1e30, 0.0)
    o1 = ops.decode_attention(q, k, v, bias)
    o2 = ref.decode_attention_ref(q.reshape(B, Hkv, H // Hkv, hd), k, v,
                                  bias).reshape(B, H, hd)
    tol = 1e-4 if dt == jnp.float32 else 3e-2
    assert float(jnp.abs((o1 - o2).astype(jnp.float32)).max()) < tol


def test_decode_attention_fully_masked_prefix_is_standalone():
    """Gate bias -inf on a fused prefix must equal attention w/o the prefix."""
    ks = jax.random.split(KEY, 4)
    B, H, Hkv, S, Sf, hd = 1, 4, 2, 64, 16, 32
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, S + Sf, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, S + Sf, hd), jnp.float32)
    bias = jnp.concatenate([jnp.full((B, Sf), -1e30), jnp.zeros((B, S))], -1)
    o_masked = ops.decode_attention(q, k, v, bias)
    o_own = ops.decode_attention(q, k[:, :, Sf:], v[:, :, Sf:], jnp.zeros((B, S)))
    assert float(jnp.abs(o_masked - o_own).max()) < 1e-5


def test_kernel_matches_model_fuser():
    """ops.fuser_mlp == core.fuser's jnp MLP on the same params."""
    from repro.core import fuser as F
    from repro.configs.case_study import tiny_zoo
    zoo = tiny_zoo()
    tx, rx = zoo["transmitters"][0], zoo["receiver"]
    fz = F.init_fuser(tx, rx, KEY)
    one = jax.tree.map(lambda a: a[0], fz["mlp"])
    x = jax.random.normal(KEY, (4, 2 * tx.kv_dim), jnp.float32)
    y_kernel = ops.fuser_mlp(one, x)
    y_jnp = F._mlp(one, x)
    assert float(jnp.abs(y_kernel - y_jnp).max()) < 1e-4


def test_project_cache_kernel_path_exact():
    """core.fuser.project_cache(use_kernel=True) routes through the Pallas
    fuser kernel and must equal the jnp path bit-for-bit (fp32, interpret)."""
    from repro.configs.case_study import tiny_zoo
    from repro.core import fuser as F
    z = tiny_zoo()
    tx, rx = z["transmitters"][0], z["receiver"]
    fz = F.init_fuser(tx, rx, KEY)
    n_tx = len(tx.attention_layers)
    st = {"k": jax.random.normal(KEY, (n_tx, 2, tx.num_kv_heads, 8,
                                       tx.resolved_head_dim)),
          "v": jax.random.normal(jax.random.fold_in(KEY, 1),
                                 (n_tx, 2, tx.num_kv_heads, 8,
                                  tx.resolved_head_dim))}
    a = F.project_cache(fz, tx, rx, st, use_kernel=False)
    b = F.project_cache(fz, tx, rx, st, use_kernel=True)
    for kk in ("k", "v", "bias"):
        assert float(jnp.abs(getattr(a, kk) - getattr(b, kk)).max()) == 0.0


# ------------------------------------------------- odd/prime S (padded tail)


@pytest.mark.parametrize("S", [13, 97, 251])
def test_decode_attention_odd_prime_S(S):
    """Odd/prime S (an unpadded fused-prefix length) must not degrade the
    block size to 1 (an S-program grid): ops pads to a lane-aligned block
    with -inf bias on the tail and unpads the output."""
    B, H, Hkv, hd = 2, 4, 2, 32
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, Hkv, S, hd))
    v = jax.random.normal(ks[2], (B, Hkv, S, hd))
    bias = jnp.where(jax.random.uniform(ks[3], (B, S)) < 0.25, -1e30, 0.0)
    o1 = ops.decode_attention(q, k, v, bias)
    o2 = ref.decode_attention_ref(q.reshape(B, Hkv, H // Hkv, hd), k, v,
                                  bias).reshape(B, H, hd)
    assert o1.shape == (B, H, hd)
    assert float(jnp.abs(o1 - o2).max()) < 1e-4


def test_decode_attention_q8_odd_S():
    B, H, Hkv, S, hd = 1, 4, 2, 37, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    kf = jax.random.normal(ks[1], (B, Hkv, S, hd))
    vf = jax.random.normal(ks[2], (B, Hkv, S, hd))
    scale = jnp.full((B, Hkv, 1, hd), 0.02, jnp.float32)
    qstack = {"k_q": jnp.clip(jnp.round(kf / 0.02), -127, 127).astype(jnp.int8),
              "v_q": jnp.clip(jnp.round(vf / 0.02), -127, 127).astype(jnp.int8),
              "k_scale": scale, "v_scale": scale}
    o1 = ops.decode_attention_q8(q, qstack, jnp.zeros((B, S)))
    o2 = ref.decode_attention_ref(
        q.reshape(B, Hkv, H // Hkv, hd),
        qstack["k_q"] * scale, qstack["v_q"] * scale,
        jnp.zeros((B, S))).reshape(B, H, hd)
    assert float(jnp.abs(o1 - o2).max()) < 1e-4


def test_banded_attention_odd_S():
    B, H, S, hd, w = 1, 2, 101, 16, 17
    ks = jax.random.split(KEY, 3)
    q, k, v = (jax.random.normal(kk, (B, H, S, hd)) for kk in ks)
    o1 = ops.banded_attention(q, k, v, window=w, block=32)
    o2 = ref.banded_attention_ref(
        q.reshape(B * H, S, hd), k.reshape(B * H, S, hd),
        v.reshape(B * H, S, hd), window=w).reshape(B, H, S, hd)
    assert o1.shape == (B, H, S, hd)
    assert float(jnp.abs(o1 - o2).max()) < 1e-4


def test_gated_fusion_odd_S():
    n, B, H, S, hd = 2, 1, 2, 37, 16
    ks = jax.random.split(KEY, 5)
    args = [jax.random.normal(k, (n, B, H, S, hd)) for k in ks[:4]]
    gate = jax.random.normal(ks[4], (n,))
    k1, v1 = ops.gated_fusion(*args, gate)
    k2, v2 = ref.gated_fusion_ref(*args, gate)
    assert k1.shape == (n, B, H, S, hd)
    assert float(jnp.abs(k1 - k2).max()) < 1e-6
    assert float(jnp.abs(v1 - v2).max()) < 1e-6


# ------------------------------------------------------ fully-masked rows


def test_decode_attention_fully_masked_rows_are_zero():
    """A row whose bias is all -inf (an empty engine slot) must emit exact
    zeros — not uniform attention over whatever garbage sits in the cache."""
    B, H, Hkv, S, hd = 2, 4, 2, 64, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, Hkv, S, hd)) * 1e6  # "uninitialized"
    v = jax.random.normal(ks[2], (B, Hkv, S, hd)) * 1e6
    bias = jnp.stack([jnp.full((S,), -1e30), jnp.zeros((S,))])  # row 0 masked
    out = ops.decode_attention(q, k, v, bias)
    assert float(jnp.abs(out[0]).max()) == 0.0
    assert float(jnp.abs(out[1]).max()) > 0.0  # live row unaffected


def test_decode_attention_q8_fully_masked_rows_are_zero():
    B, H, Hkv, S, hd = 1, 2, 1, 32, 16
    q = jax.random.normal(KEY, (B, H, hd))
    scale = jnp.full((B, Hkv, 1, hd), 1e4, jnp.float32)  # huge garbage KV
    qstack = {"k_q": jnp.full((B, Hkv, S, hd), 127, jnp.int8),
              "v_q": jnp.full((B, Hkv, S, hd), 127, jnp.int8),
              "k_scale": scale, "v_scale": scale}
    out = ops.decode_attention_q8(q, qstack, jnp.full((B, S), -1e30))
    assert float(jnp.abs(out).max()) == 0.0


def test_decode_attention_pallas_bad_block_raises():
    """The shape precondition must survive python -O: ValueError, not assert."""
    from repro.kernels.decode_attention import (decode_attention_pallas,
                                                decode_attention_q8_pallas)
    B, Hkv, G, S, hd = 1, 1, 2, 24, 16
    q = jnp.zeros((B, Hkv, G, hd))
    k = jnp.zeros((B, Hkv, S, hd))
    bias = jnp.zeros((B, S))
    with pytest.raises(ValueError, match="not divisible"):
        decode_attention_pallas(q, k, k, bias, block_s=16, interpret=True)
    scale = jnp.zeros((B, Hkv, 1, hd))
    with pytest.raises(ValueError, match="not divisible"):
        decode_attention_q8_pallas(q, k.astype(jnp.int8), k.astype(jnp.int8),
                                   scale, scale, bias, block_s=16,
                                   interpret=True)


# ------------------------------------------------------- paged attention


def _paged_case(page_size, *, dt=jnp.float32):
    """Pool + page maps exercising partial final pages, interleaved
    INVALID_PAGE entries and a fully-evicted slot."""
    Hkv, G, hd = 2, 4, 32
    pps = 4
    num_pages = 3 * pps
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (5, Hkv * G, hd), jnp.float32).astype(dt)
    k_pool = jax.random.normal(
        ks[1], (num_pages, Hkv, page_size, hd), jnp.float32).astype(dt)
    v_pool = jax.random.normal(
        ks[2], (num_pages, Hkv, page_size, hd), jnp.float32).astype(dt)
    INV = num_pages
    pm = jnp.array([
        [3, 7, INV, INV],        # partial final page
        [0, INV, 5, INV],        # INVALID interleaved inside the map
        [1, 2, 4, 6],            # fully mapped
        [INV, INV, INV, INV],    # evicted slot
        [8, 9, INV, 11],         # INVALID inside the live length
    ], jnp.int32)
    lengths = jnp.array([page_size + 3, page_size - 2, 4 * page_size,
                         2, 2 * page_size + 1], jnp.int32)
    return q, k_pool, v_pool, pm, lengths


# geometries (Hkv, G, hd, page_size) at which a slot spans several of the
# kernel's page blocks; the last is Qwen3-0.6B's, the one before Qwen2.5-0.5B's
_BLOCK_GEOMETRY = {
    "edges": (2, 4, 32, 16),
    "holes": (2, 4, 32, 16),
    "qwen2.5-0.5b": (2, 7, 64, 16),
    "qwen3-0.6b": (8, 2, 128, 16),
}


def _paged_blocks_case(name, itemsize):
    """Slots over several page blocks: P pages a block, as the kernel picks
    it for a pool of this itemsize, and 2P + 3 pages a slot, so the map is no
    multiple of P wide and a full slot wraps the double buffer. Every slot
    maps real pages past its length, as a lease does. ``edges``: lengths on
    a block boundary, one past it, inside the first page, 0 and full.
    ``holes``: INVALID pages inside the length and inside one block, a live
    slot whose second block maps no page, and an evicted slot."""
    from repro.kernels.paged_attention import block_pages
    Hkv, G, hd, pg = _BLOCK_GEOMETRY[name]
    P = block_pages((1, Hkv, pg, hd), itemsize, 1 << 20)
    pps, blk = 2 * P + 3, P * pg
    if name == "holes":
        lengths = [pps * pg, 2 * blk + 5, blk + 7, 2]
        holes = ([(0, 1), (0, 2), (0, pps - 1)]
                 + [(1, P + j) for j in range(P)] + [(2, P)]
                 + [(3, j) for j in range(pps)])
    else:
        lengths, holes = [blk, blk + 1, 3, 0, pps * pg], []
    slots = len(lengths)
    num_pages = slots * pps
    ks = jax.random.split(jax.random.fold_in(KEY, len(name)), 4)
    q = jax.random.normal(ks[0], (slots, Hkv * G, hd), jnp.float32)
    k_pool, v_pool = (jax.random.normal(k, (num_pages, Hkv, pg, hd),
                                        jnp.float32) for k in ks[1:3])
    pm = jax.random.permutation(ks[3], num_pages).reshape(slots, pps)
    for s, j in holes:
        pm = pm.at[s, j].set(num_pages)
    return q, k_pool, v_pool, pm.astype(jnp.int32), jnp.array(lengths,
                                                              jnp.int32)


def _paged_cases(*page_sizes):
    return ([pytest.param(pg, id=str(pg)) for pg in page_sizes]
            + list(_BLOCK_GEOMETRY))


def _case(case, itemsize):
    if isinstance(case, int):
        return _paged_case(case)
    return _paged_blocks_case(case, itemsize)


def _check_paged(out, l, q, k_pool, v_pool, pm, lengths):
    """Kernel output == gather oracle; a slot with no live key returns zeros
    with zero attention mass (hardened finish), every other slot has l > 0."""
    slots, H, hd = q.shape
    Hkv = k_pool.shape[1]
    oref = ref.paged_decode_attention_ref(
        q.reshape(slots, Hkv, H // Hkv, hd), k_pool, v_pool, pm,
        lengths).reshape(slots, H, hd)
    assert float(jnp.abs(out - oref).max()) < 1e-4
    t = jnp.arange(pm.shape[1] * k_pool.shape[2])
    mapped = jnp.repeat(pm < k_pool.shape[0], k_pool.shape[2], axis=1)
    seen = (mapped & (t < lengths[:, None])).any(axis=1)
    assert float(jnp.abs(jnp.where(seen[:, None, None], 0.0, out)).max()) == 0
    assert bool(jnp.where(seen[:, None], l > 0, l == 0).all())


@pytest.mark.parametrize("pool_shape,itemsize,pps,P", [
    ((8192, 8, 16, 128), 2, 256, 16),  # Qwen3-0.6B, bf16: the cells' pool
    ((8192, 8, 16, 128), 1, 256, 32),  # the same pool in int8
    ((1024, 2, 16, 64), 2, 128, 64),   # Qwen2.5-0.5B: heads padded to 128
    ((64, 2, 8, 32), 4, 4, 4),         # a small pool: one block a slot
])
def test_paged_block_pages_from_shape(pool_shape, itemsize, pps, P):
    """P, the pages of one block, comes from the page's byte size alone: the
    most pages whose K block fits in BLOCK_BYTES, at most a slot's map."""
    from repro.kernels.paged_attention import block_pages
    assert block_pages(pool_shape, itemsize, pps) == P


@pytest.mark.parametrize("case", _paged_cases(8, 16, 64))
def test_paged_decode_attention_matches_gather_ref(case):
    """In-place page-map walk == gather-then-attend oracle, across page sizes,
    partial final pages, interleaved INVALID_PAGE entries and slots that
    span several page blocks."""
    q, k_pool, v_pool, pm, lengths = _case(case, 4)
    out, m, l = ops.paged_decode_attention(q, k_pool, v_pool, pm, lengths)
    _check_paged(out, l, q, k_pool, v_pool, pm, lengths)


@pytest.mark.parametrize("case", _paged_cases(8, 16))
def test_paged_decode_attention_q8_matches_ref(case):
    q, k_pool, v_pool, pm, lengths = _case(case, 1)
    Hkv = k_pool.shape[1]
    num_pages, hd = k_pool.shape[0], k_pool.shape[3]
    sk = jnp.max(jnp.abs(k_pool), axis=2, keepdims=True) / 127.0
    sv = jnp.max(jnp.abs(v_pool), axis=2, keepdims=True) / 127.0
    qpool = {
        "k_q": jnp.clip(jnp.round(k_pool / sk), -127, 127).astype(jnp.int8),
        "v_q": jnp.clip(jnp.round(v_pool / sv), -127, 127).astype(jnp.int8),
        "k_scale": sk, "v_scale": sv,
    }
    assert qpool["k_scale"].shape == (num_pages, Hkv, 1, hd)
    out, _, l = ops.paged_decode_attention_q8(q, qpool, pm, lengths)
    _check_paged(out, l, q, qpool["k_q"] * sk, qpool["v_q"] * sv, pm,
                 lengths)


def test_paged_decode_attention_lse_stats_merge():
    """The kernel's (m, l) statistics LSE-merge two disjoint page sets to the
    same result as attending over their union — the property the fused-prefix
    merge path relies on."""
    from repro.models.attention import merge_attention
    page_size, Hkv, G, hd = 8, 2, 2, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, Hkv * G, hd))
    k_pool = jax.random.normal(ks[1], (4, Hkv, page_size, hd))
    v_pool = jax.random.normal(ks[2], (4, Hkv, page_size, hd))
    pm_all = jnp.array([[0, 1, 2, 3]], jnp.int32)
    both = ops.paged_decode_attention(q, k_pool, v_pool, pm_all,
                                      jnp.array([32], jnp.int32))[0]
    INV = 4
    parts = []
    for pm in ([[0, 1, INV, INV]], [[INV, INV, 2, 3]]):
        o, m, l = ops.paged_decode_attention(q, k_pool, v_pool,
                                             jnp.array(pm, jnp.int32),
                                             jnp.array([32], jnp.int32))
        parts.append(((o * l[..., None])[:, :, None, :], m[:, :, None],
                      l[:, :, None]))
    merged = merge_attention(parts).reshape(both.shape)
    assert float(jnp.abs(merged - both).max()) < 1e-4


def test_paged_decode_attention_bad_shapes_raise():
    q = jnp.zeros((2, 2, 2, 16))
    pool = jnp.zeros((4, 2, 8, 16))
    with pytest.raises(ValueError, match="page_map"):
        from repro.kernels.paged_attention import paged_decode_attention_pallas
        paged_decode_attention_pallas(q, pool, pool,
                                      jnp.zeros((3, 2), jnp.int32),
                                      jnp.zeros((2,), jnp.int32),
                                      interpret=True)


def test_slot_table_write_token_respects_invalid_pages():
    """SlotTable.write_token scatters each slot's token to its physical page
    and drops writes through INVALID_PAGE (evicted slots can't corrupt the
    pool)."""
    from repro.models.cache import SlotTable
    Hkv, pg, hd = 2, 8, 16
    pool = jnp.zeros((4, Hkv, pg, hd))
    pm = jnp.array([[2, 0], [4, 4]], jnp.int32)  # slot 1 evicted (INVALID=4)
    tok = jnp.ones((2, Hkv, hd))
    out = SlotTable.write_token(pool, tok, pm, jnp.array([9, 3]), pg)
    # slot 0: pos 9 -> page_idx 1 -> phys pm[0,1] == 0, offset 1
    assert float(jnp.abs(out[0, :, 1] - 1.0).max()) == 0.0
    out = out.at[0, :, 1].set(0.0)
    # ... and nothing else was touched (slot 1's write dropped through INVALID)
    assert float(jnp.abs(out).max()) == 0.0


# ------------------------------------------------- ragged varlen prefill


def _ragged_case(page_size, block_q, *, dt=jnp.float32):
    """Packed varlen chunk over a paged pool: three sequences at different
    position offsets (a chunked continuation, a fresh short prompt, a suffix
    after a shared prefix), ragged tails, a trailing dead pad block, and
    INVALID pages past each sequence's live range. Returns the packed operands
    plus per-sequence dense (q, k, v, base, rows) for the monolithic oracle."""
    Hkv, G, hd = 2, 2, 16
    H = Hkv * G
    pps = 4
    num_pages = 3 * pps
    INV = num_pages
    # (first query position, #queries); context covers [0, base + nq)
    seqs = [(page_size + 1, page_size - 1), (0, 5), (3, 2 * page_size)]
    ks = jax.random.split(KEY, 2 + 3 * len(seqs))
    # unmapped pages hold huge garbage: masking must keep it out entirely
    k_pool = jax.random.normal(ks[0], (num_pages, Hkv, page_size, hd),
                               jnp.float32) * 1e3
    v_pool = jax.random.normal(ks[1], (num_pages, Hkv, page_size, hd),
                               jnp.float32) * 1e3
    pm = jnp.full((len(seqs), pps), INV, jnp.int32)
    next_page = 0
    packed_q, block_seq, block_pos, block_len, dense = [], [], [], [], []
    for i, (base, nq) in enumerate(seqs):
        ctx = base + nq
        n_pages = -(-ctx // page_size)
        pages = jnp.arange(next_page, next_page + n_pages)
        next_page += n_pages
        pm = pm.at[i, :n_pages].set(pages)
        kk = jax.random.split(ks[2 + i], 3)
        kd = jax.random.normal(kk[0], (ctx, Hkv, hd), jnp.float32)
        vd = jax.random.normal(kk[1], (ctx, Hkv, hd), jnp.float32)
        qd = jax.random.normal(kk[2], (nq, H, hd), jnp.float32)
        pad = n_pages * page_size - ctx
        put = lambda pool, d: pool.at[pages].set(
            jnp.pad(d, ((0, pad), (0, 0), (0, 0)))
            .reshape(n_pages, page_size, Hkv, hd).transpose(0, 2, 1, 3))
        k_pool = put(k_pool, kd)
        v_pool = put(v_pool, vd)
        n_blk = -(-nq // block_q)
        start = sum(a.shape[0] for a in packed_q)
        packed_q.append(jnp.pad(qd, ((0, n_blk * block_q - nq),
                                     (0, 0), (0, 0))))
        for b in range(n_blk):
            block_seq.append(i)
            block_pos.append(base + b * block_q)
            block_len.append(min(block_q, nq - b * block_q))
        rows = [start + b * block_q + t
                for b in range(n_blk)
                for t in range(min(block_q, nq - b * block_q))]
        dense.append((qd, kd, vd, base, rows))
    packed_q.append(jnp.zeros((block_q, H, hd)))  # dead pad block
    block_seq.append(-1)
    block_pos.append(0)
    block_len.append(0)
    q = jnp.concatenate(packed_q).astype(dt)
    mk = lambda xs: jnp.asarray(xs, jnp.int32)
    return (q, k_pool.astype(dt), v_pool.astype(dt), mk(block_seq),
            mk(block_pos), mk(block_len), pm, dense)


def _dense_causal_chunk(qd, kd, vd, base):
    """Monolithic padded-prefill oracle: chunk queries at absolute offset
    ``base`` attend densely + causally over the full context [0, base+nq)."""
    nq, H, hd = qd.shape
    Hkv = kd.shape[1]
    qg = qd.reshape(nq, Hkv, H // Hkv, hd)
    s = jnp.einsum("qkgd,tkd->kgqt", qg.astype(jnp.float32),
                   kd.astype(jnp.float32)) * (hd ** -0.5)
    causal = jnp.arange(kd.shape[0])[None, :] <= (base + jnp.arange(nq))[:, None]
    s = jnp.where(causal[None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("kgqt,tkd->kgqd", w, vd.astype(jnp.float32))
    return out.transpose(2, 0, 1, 3).reshape(nq, H, hd).astype(qd.dtype)


@pytest.mark.parametrize("page_size", [4, 8, 16])
@pytest.mark.parametrize("block_q", [4, 8])
def test_ragged_prefill_matches_gather_ref(page_size, block_q):
    """Scalar-prefetch page-map walk == gather-then-attend oracle across page
    sizes and block widths; ragged tails, pad blocks and unmapped pages emit
    exact zeros with zero attention mass (hardened finish)."""
    q, k_pool, v_pool, bs, bp, bl, pm, _ = _ragged_case(page_size, block_q)
    out, m, l = ops.ragged_prefill_attention(q, k_pool, v_pool, bs, bp, bl,
                                             pm, block_q=block_q)
    oref = ref.ragged_prefill_attention_ref(q, k_pool, v_pool, bs, bp, bl,
                                            pm, block_q=block_q)
    assert float(jnp.abs(out - oref).max()) < 1e-4
    live = (jnp.arange(block_q)[None] < bl[:, None]).reshape(-1)
    assert float(jnp.abs(out[~live]).max()) == 0.0
    assert float(l[~live].max()) == 0.0
    assert bool((l[live] > 0).all())


@pytest.mark.parametrize("page_size", [4, 8, 16])
def test_ragged_prefill_token_identical_to_dense(page_size):
    """Each packed sequence's rows equal a monolithic dense causal prefill of
    the same chunk — across position offsets and ragged prompt lengths. This
    is the invariant that makes chunked == monolithic prefill token-identical."""
    block_q = 8
    q, k_pool, v_pool, bs, bp, bl, pm, dense = _ragged_case(page_size, block_q)
    out, _, _ = ops.ragged_prefill_attention(q, k_pool, v_pool, bs, bp, bl,
                                             pm, block_q=block_q)
    for qd, kd, vd, base, rows in dense:
        want = _dense_causal_chunk(qd, kd, vd, base)
        assert float(jnp.abs(out[jnp.asarray(rows)] - want).max()) < 1e-4


def test_ragged_prefill_lse_stats_merge():
    """The kernel's (m, l) statistics LSE-merge two disjoint page subsets of
    one sequence to the same result as its full page map — the property the
    fused-prefix merge in the chunked prefill path relies on."""
    from repro.models.attention import merge_attention
    page_size, Hkv, G, hd, bq = 8, 2, 2, 16, 8
    H = Hkv * G
    nq = 2 * page_size
    base = 2 * page_size  # queries sit over pages 2..3; pages 0..1 are context
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (nq, H, hd))
    k_pool = jax.random.normal(ks[1], (4, Hkv, page_size, hd))
    v_pool = jax.random.normal(ks[2], (4, Hkv, page_size, hd))
    n_blk = nq // bq
    bs = jnp.zeros((n_blk,), jnp.int32)
    bp = base + jnp.arange(n_blk, dtype=jnp.int32) * bq
    bl = jnp.full((n_blk,), bq, jnp.int32)
    full, _, _ = ops.ragged_prefill_attention(
        q, k_pool, v_pool, bs, bp, bl, jnp.array([[0, 1, 2, 3]], jnp.int32),
        block_q=bq)
    INV = 4
    parts = []
    for pm in ([[0, 1, INV, INV]], [[INV, INV, 2, 3]]):
        o, m, l = ops.ragged_prefill_attention(
            q, k_pool, v_pool, bs, bp, bl, jnp.array(pm, jnp.int32),
            block_q=bq)
        # (T, H, ...) -> (1, H, T, ...) part layout merge_attention expects
        parts.append(((o * l[..., None]).transpose(1, 0, 2)[None],
                      m.T[None], l.T[None]))
    merged = merge_attention(parts).reshape(nq, H, hd)
    assert float(jnp.abs(merged - full).max()) < 1e-4


def test_ragged_prefill_bad_shapes_raise():
    from repro.kernels.prefill_attention import ragged_prefill_attention_pallas
    pool = jnp.zeros((4, 2, 8, 16))
    bs = jnp.zeros((2,), jnp.int32)
    pm = jnp.zeros((1, 2), jnp.int32)
    with pytest.raises(ValueError, match="not divisible"):
        ops.ragged_prefill_attention(jnp.zeros((10, 4, 16)), pool, pool,
                                     bs, bs, bs, pm, block_q=8)
    with pytest.raises(ValueError, match="does not match pool"):
        ragged_prefill_attention_pallas(jnp.zeros((2, 1, 8, 16)), pool, pool,
                                        bs, bs, bs, pm, block_q=8,
                                        interpret=True)
    with pytest.raises(ValueError, match="block_pos"):
        ragged_prefill_attention_pallas(jnp.zeros((2, 2, 8, 16)), pool, pool,
                                        bs, jnp.zeros((3,), jnp.int32), bs,
                                        pm, block_q=8, interpret=True)
    with pytest.raises(ValueError, match="page_map"):
        ragged_prefill_attention_pallas(jnp.zeros((2, 2, 8, 16)), pool, pool,
                                        bs, bs, bs, jnp.zeros((2,), jnp.int32),
                                        block_q=8, interpret=True)


@pytest.mark.parametrize("S,hd,w,blk", [
    (256, 32, 64, 64),
    pytest.param(512, 64, 100, 128, marks=pytest.mark.slow),  # largest interp case
    (128, 16, 16, 32), (64, 32, 64, 64),
])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_banded_attention_sweep(S, hd, w, blk, dt):
    """Banded kernel == dense masked reference; grid never launches blocks
    outside the diagonal band (O(S·window) structural win)."""
    ks = jax.random.split(KEY, 3)
    B, H = 1, 2
    q = jax.random.normal(ks[0], (B, H, S, hd), jnp.float32).astype(dt)
    k = jax.random.normal(ks[1], (B, H, S, hd), jnp.float32).astype(dt)
    v = jax.random.normal(ks[2], (B, H, S, hd), jnp.float32).astype(dt)
    o1 = ops.banded_attention(q, k, v, window=w, block=blk)
    o2 = ref.banded_attention_ref(
        q.reshape(B * H, S, hd), k.reshape(B * H, S, hd),
        v.reshape(B * H, S, hd), window=w).reshape(B, H, S, hd)
    tol = 1e-4 if dt == jnp.float32 else 5e-2
    assert float(jnp.abs((o1 - o2).astype(jnp.float32)).max()) < tol
