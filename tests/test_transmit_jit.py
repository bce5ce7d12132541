"""The C2C transmit side as two compiled programs: the transmitter's prefill
and export (``fedrefine.transmit_program``) and the receiver's fuser and gate
(``fedrefine.fuse_program``). Each equals the eager path it replaces, and a
submit builds them once per prompt length, then reuses them.

Prompt lengths differ between tests: the programs are module-level, so a
length one test compiled would already be in the cache of the next."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.case_study import tiny_zoo
from repro.core import c2c
from repro.core.fedrefine import (FedRefineSystem, Participant, fuse_program,
                                  transmit_program)
from repro.models import transformer as T

VOCAB = 64
KEY = jax.random.PRNGKey(5)


@pytest.fixture(scope="module")
def members():
    zoo = tiny_zoo(vocab_size=VOCAB)
    cfgs = [zoo["receiver"], *zoo["transmitters"][:2]]
    return [Participant(c.name, c, T.init_params(
        c, jax.random.fold_in(KEY, i), jnp.float32))
        for i, c in enumerate(cfgs)]


def _assert_close(got, want, rtol=0.0):
    for name in ("k", "v", "bias"):
        a, b = getattr(got, name, None), getattr(want, name, None)
        if b is None:
            assert a is None, name
            continue
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=rtol, atol=1e-5, err_msg=name)


@pytest.mark.parametrize("S", [7, 12])
def test_transmit_program_equals_eager_prefill_and_export(members, S):
    tx = members[1]
    tokens = jax.random.randint(jax.random.fold_in(KEY, S), (1, S), 0, VOCAB)
    _, cache = T.prefill(tx.cfg, tx.params, tokens, max_seq=S)
    got = transmit_program(tx.cfg, tx.params, tokens)
    want = cache.export_stack(tx.cfg, length=S)
    # the stack is bf16, as it is shipped: float32 round-off may carry a value
    # across a bf16 rounding boundary, one bf16 step (2**-8 to 2**-7 of it)
    _assert_close(got, want, rtol=2.0 ** -7)
    for name in ("k", "v"):
        moved = np.asarray(getattr(got, name) != getattr(want, name))
        assert moved.mean() < 0.01, (name, moved.mean())


@pytest.mark.parametrize("n_tx", [1, 2])
@pytest.mark.parametrize("gated", [True, False])
def test_fuse_program_equals_eager_fused_prefix(members, gated, n_tx):
    system = FedRefineSystem.build(members, seed=1)
    rx, txs = members[0], members[1:1 + n_tx]
    S = 6
    stacks = []
    for i, tx in enumerate(txs):
        tokens = jax.random.randint(jax.random.fold_in(KEY, 100 + i), (1, S),
                                    0, VOCAB)
        _, cache = T.prefill(tx.cfg, tx.params, tokens, max_seq=S)
        stacks.append(cache.export_stack(tx.cfg, length=S))
    fusers = [system.registry.get(tx.name, rx.name) for tx in txs]
    gating = system.registry.ensure_gating(rx.name) if gated else None
    want = c2c.fused_prefix(fusers, [tx.cfg for tx in txs], rx.cfg, stacks,
                            gating=gating)
    got = fuse_program(fusers, gating, stacks,
                       cfg_txs=tuple(tx.cfg for tx in txs), cfg_rx=rx.cfg)
    assert got.k.shape[-2] == n_tx * S
    _assert_close(got, want)
    _assert_close(system.fused_prefix(rx.name, [tx.name for tx in txs],
                                      stacks, gated=gated), want)


def test_submits_build_one_program_per_prompt_length(members, trace_guard):
    system = FedRefineSystem.build(members[:2])
    rx = members[0].name
    system.make_engine(rx, max_slots=4, max_seq=64, max_prefix=16)
    # build the engine's own programs outside the guarded windows
    system.submit(rx, jnp.arange(3, dtype=jnp.int32)[None], 2,
                  protocol="standalone")
    system.drain(rx)

    once = {"transmit_program": 1, "fuse_program": 1}
    with trace_guard(exact=once):
        for i in range(3):
            system.submit(rx, jnp.full((1, 9), i, jnp.int32), 2,
                          protocol="c2c")
    system.drain(rx)
    assert system.stats == {"tx_calls": 3, "tx_programs": 1}

    with trace_guard(exact=once):
        system.submit(rx, jnp.full((1, 10), 3, jnp.int32), 2, protocol="c2c")
    done, = system.drain(rx).values()
    assert system.stats == {"tx_calls": 4, "tx_programs": 2}
    assert done["protocol"] == "c2c" and len(done["tokens"]) == 2
