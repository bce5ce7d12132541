"""The served Pallas kernels compile for a TPU v5e, with no chip attached.

The TPU compiler is installed with jax; it compiles for a described
``v5e:2x2`` topology. This catches what interpret mode cannot: Mosaic's
block-shape rules, VMEM limits, unaligned slices. The kernels run at the
widths of the case-study pairing (Qwen3-0.6B receiver, Qwen2.5-0.5B
transmitter) and the chip smoke run's geometry: 8 slots of 2,048 tokens in
16-token bf16 pages, 256-token prefill chunks; the paged decode kernel also
at the benchmark cells' serving geometry.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.case_study import QWEN2_5_0_5B, QWEN3_0_6B
from repro.kernels.paged_attention import paged_decode_attention_pallas
from repro.kernels.prefill_attention import ragged_prefill_attention_pallas

SLOTS, MAX_SEQ, PAGE, CHUNK = 8, 2048, 16, 256
PPS = MAX_SEQ // PAGE
NUM_PAGES = SLOTS * PPS

CONFIGS = [QWEN3_0_6B, QWEN2_5_0_5B]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip cannot be read back without one: keep
    these compiles out of the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _dims(cfg):
    hkv = cfg.num_kv_heads
    return hkv, cfg.num_heads // hkv, cfg.resolved_head_dim


def _compiled_text(fn, args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("cfg,slots,pps,num_pages", [
    pytest.param(c, SLOTS, PPS, NUM_PAGES, id=c.name) for c in CONFIGS] + [
    # the benchmark cells' serving geometry: 32 slots of 256 pages mapped
    # into a 2,048-page pool
    pytest.param(QWEN3_0_6B, 32, 256, 2048, id="qwen3-0.6b-serve")])
def test_paged_decode_compiles_for_v5e(cfg, slots, pps, num_pages, one_chip,
                                       no_persistent_cache):
    hkv, g, hd = _dims(cfg)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = s((num_pages, hkv, PAGE, hd), jnp.bfloat16)
    args = (s((slots, hkv, g, hd), jnp.bfloat16), pool, pool,
            s((slots, pps), jnp.int32), s((slots,), jnp.int32))
    text = _compiled_text(paged_decode_attention_pallas, args)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("cfg", CONFIGS, ids=lambda c: c.name)
def test_ragged_prefill_compiles_for_v5e(cfg, one_chip, no_persistent_cache):
    hkv, g, hd = _dims(cfg)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = s((NUM_PAGES, hkv, PAGE, hd), jnp.bfloat16)
    meta = s((1,), jnp.int32)  # one chunk-wide query block
    args = (s((1, hkv, g * CHUNK, hd), jnp.bfloat16), pool, pool,
            meta, meta, meta, s((1, PPS), jnp.int32))
    text = _compiled_text(
        lambda *a: ragged_prefill_attention_pallas(*a, block_q=CHUNK), args)
    assert "tpu_custom_call" in text
