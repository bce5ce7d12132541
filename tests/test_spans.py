"""The program's own trace spans (``fr.*``) and the engine's queue-wait
counter: the names and nesting a trace of one C2C submit and two engine steps
holds, the stats the spans carry, and ``queue_wait_us`` / ``reserved`` on each
path a request leaves the queue by."""
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.configs.case_study import tiny_zoo
from repro.core.fedrefine import FedRefineSystem, Participant
from repro.launch.engine import ContinuousBatchingEngine
from repro.models import transformer as T

VOCAB = 64

#: every program span and the program span it lies directly under
PARENT = {
    "fr.submit": None,
    "fr.prepare": "fr.submit",
    "fr.tx_prefill": "fr.prepare",
    "fr.tx_export": "fr.prepare",
    "fr.wire": "fr.prepare",
    "fr.fuse": "fr.prepare",
    "fr.enqueue": "fr.submit",
    "fr.pad": "fr.enqueue",
    "fr.digest": "fr.enqueue",
    "fr.fetch": "fr.digest",
    "fr.hash": "fr.digest",
    "fr.step": None,
    "fr.admit": "fr.step",
    "fr.chunk": "fr.step",
    "fr.decode": "fr.step",
    "fr.sync": "fr.step",
    "fr.retire": "fr.step",
}


def _program_events(trace_dir):
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            got = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in line.events
                   if e.name.startswith("fr.")]
            if got:
                lines[(plane.name, line.name)] = got
    return lines


def _innermost_parent(events, i):
    """The shortest other program span that contains span ``i``."""
    name, a, b, _ = events[i]
    best = None
    for j, (n, s, e, _) in enumerate(events):
        if j != i and s <= a and b <= e and (best is None or e - s < best[1]):
            best = (n, e - s)
    return best[0] if best else None


def test_a_traced_submit_and_two_steps_hold_every_span_nested(tmp_path):
    zoo = tiny_zoo(vocab_size=VOCAB)
    key = jax.random.PRNGKey(3)
    members = [Participant(c.name, c, T.init_params(
        c, jax.random.fold_in(key, i), jnp.float32))
        for i, c in enumerate([zoo["receiver"], zoo["transmitters"][0]])]
    system = FedRefineSystem.build(members)
    rx, tx = members[0].name, members[1].name
    eng = system.make_engine(rx, max_slots=2, max_seq=64, max_prefix=16,
                             paged=True, page_size=8, num_pages=32,
                             prefill_token_budget=8)
    prompt = jax.random.randint(key, (1, 12), 0, VOCAB)
    # compile every program outside the trace: 12 rows are two chunks
    system.submit(rx, prompt + 1, 3, protocol="c2c")
    system.drain(rx)
    before = dict(eng.stats)

    jax.profiler.start_trace(str(tmp_path))
    try:
        rid = system.submit(rx, prompt, 3, protocol="c2c")
        eng.step()  # the first chunk: no slot is active yet
        eng.step()  # the final chunk, then the decode
    finally:
        jax.profiler.stop_trace()

    lines = _program_events(str(tmp_path))
    assert len(lines) == 1, f"program spans on several lines: {list(lines)}"
    events, = lines.values()
    names = {e[0] for e in events}
    assert names == set(PARENT), names ^ set(PARENT)
    for i, (name, *_rest) in enumerate(events):
        assert _innermost_parent(events, i) == PARENT[name], name

    stats = {n: [e[3] for e in events if e[0] == n] for n in names}
    assert stats["fr.submit"] == [{"protocol": "c2c"}]
    assert stats["fr.tx_prefill"] == [{"tx": tx}]
    assert stats["fr.enqueue"] == [{"rid": rid}]
    chunks = [(s["rid"], s["start"], s["rows"]) for s in stats["fr.chunk"]]
    assert chunks == [(rid, 0, 8), (rid, 8, 4)]
    assert sum(r for _, _, r in chunks) == \
        eng.stats["prefill_tokens"] - before["prefill_tokens"]
    assert len(chunks) == \
        eng.stats["prefill_chunks"] - before["prefill_chunks"]
    assert sum(n == "fr.step" for n, *_ in events) == 2
    assert sum(n == "fr.decode" for n, *_ in events) == 1


# ------------------------------------------------------------- queue wait


@pytest.fixture(scope="module")
def cfg():
    return ModelConfig(name="spans-tiny", family="dense", num_layers=2,
                       d_model=32, num_heads=2, num_kv_heads=1, head_dim=16,
                       d_ff=64, vocab_size=VOCAB, tie_embeddings=True)


@pytest.fixture(scope="module")
def params(cfg):
    return T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)


def _prompt(seed, n):
    return jax.random.randint(jax.random.PRNGKey(seed), (1, n), 0, VOCAB)


def test_the_second_request_waits_at_least_the_first_ones_slot_time(
        cfg, params):
    eng = ContinuousBatchingEngine(cfg, params, max_slots=1, max_seq=64,
                                   paged=True, page_size=8, num_pages=16,
                                   prefill_token_budget=8)
    eng.submit(_prompt(1, 6), 4)
    eng.submit(_prompt(2, 6), 4)
    t_first = time.perf_counter()  # before the step that reserves the first
    eng.step()
    assert eng.stats["reserved"] == 1
    first_wait = eng.stats["queue_wait_us"]
    while eng.stats["completed"] == 0:
        eng.step()
    t_freed = time.perf_counter()  # the step that evicted the first ended
    assert eng.stats["reserved"] == 1 and eng.num_queued == 1
    eng.step()
    assert eng.stats["reserved"] == 2
    second_wait_s = (eng.stats["queue_wait_us"] - first_wait) * 1e-6
    assert second_wait_s >= t_freed - t_first
    eng.drain()
    assert eng.stats["reserved"] == 2


def _engine(cfg, params, path):
    kw = dict(max_slots=2, max_seq=64, paged=True, page_size=8,
              num_pages=32)
    if path.startswith("chunked"):
        kw["prefill_token_budget"] = 8
    return ContinuousBatchingEngine(cfg, params, **kw)


@pytest.mark.parametrize("path", ["chunked_fresh", "chunked_shared",
                                  "monolithic_fresh", "monolithic_shared"])
def test_reserved_counts_each_request_once(cfg, params, path):
    eng = _engine(cfg, params, path)
    base = np.asarray(_prompt(5, 16))
    prompts = ([base, np.asarray(_prompt(6, 9)), np.asarray(_prompt(7, 5))]
               if path.endswith("fresh") else
               # the later two extend the first's 16 tokens (two pages)
               [base] + [np.concatenate([base, np.asarray(_prompt(s, 4))], 1)
                         for s in (8, 9)])
    eng.submit(prompts[0], 3)
    eng.drain()
    assert eng.stats["reserved"] == 1
    for p in prompts[1:]:
        eng.submit(p, 3)
    hits = eng.stats["radix_hits"]
    while eng.num_queued:
        eng.step()
        # a request leaves the queue once and is counted then
        assert eng.stats["reserved"] == len(prompts) - eng.num_queued
    eng.drain()
    assert eng.stats["reserved"] == len(prompts) == eng.stats["admitted"]
    assert (eng.stats["radix_hits"] > hits) == path.endswith("shared")
    assert eng.stats["queue_wait_us"] > 0
