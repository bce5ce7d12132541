"""The chip smoke run's phases (chip_smoke.py at the repo root), rehearsed on
the CPU at tiny_zoo widths with the Pallas kernels in interpret mode: the
same build, serve and reference-check code the TPU run executes at published
widths."""
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.case_study import tiny_zoo

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# 60 prompt tokens take four 16-token chunks; 30 shared tokens are three
# full 8-token pages plus six tokens of a fourth, so the radix hit copies it
TINY = dict(max_slots=4, max_seq=128, page_size=8, prefill_budget=16,
            max_prefix=32, long_prompt=60, shared_prefix=30, tail=10,
            c2c_prompt=20, short_prompt=12, new_tokens=4)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses resolve their module
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def served(smoke):
    zoo = tiny_zoo()
    rx, tx = zoo["receiver"], zoo["transmitters"][0]
    sizes = smoke.Sizes(**TINY)
    system = smoke.build(rx, tx, seed=0)
    requests = smoke.make_requests(rx.vocab_size, sizes, seed=0)
    done, eng = smoke.serve(system, rx.name, requests, sizes)
    return system, rx, sizes, requests, done, eng


def test_smoke_serves_every_protocol_through_every_mechanism(smoke, served):
    _, _, _, requests, done, eng = served
    smoke.check_served(requests, done, eng.stats)
    assert {d["protocol"] for d in done.values()} == {"standalone", "c2c",
                                                      "t2t"}
    assert eng.stats["prefill_chunks"] > len(requests)
    assert eng.stats["radix_matched_tokens"] == TINY["shared_prefix"]
    # off the chip the kernels are interpreted: no Mosaic custom call
    assert "tpu_custom_call" not in eng.lower_decode().as_text()
    assert eng.stats["decode_traces"] == 1  # lowering reused the trace


def test_smoke_logits_match_float32_reference(smoke, served):
    system, rx, sizes, requests, done, eng = served
    report = smoke.check_reference(system, eng, rx.name, requests, done)
    assert set(report) == {r.name for r in requests} | {"control_fp8_weights"}
    for r in requests:  # every request's every step, the engine's own tokens
        assert report[r.name]["steps"] == r.steps
        assert report[r.name]["worst_gap_rms"] <= smoke.TIE_TOL
    for name in ("long", "c2c"):
        assert report[name]["last_prompt"] <= smoke.REL_TOL
        assert report[name]["first_decode"] <= smoke.REL_TOL
    # the fused prefix moves the logits further than bf16 rounding does
    assert report["c2c"]["without_prefix"] > report["c2c"]["last_prompt"]
    assert report["control_fp8_weights"]["last_prompt"] > smoke.REL_TOL


@pytest.mark.parametrize("name", ["shared", "t2t"])
def test_smoke_reference_rejects_a_wrong_token_stream(smoke, served, name):
    """The token check holds the engine's own output: a stream whose tokens
    the reference does not rate fails it, on the radix-hit (CoW) request and
    on the T2T one alike."""
    system, rx, _, requests, done, eng = served
    toks = np.asarray(done[name]["tokens"])
    ref = smoke.reference_logits(
        rx, jax.tree.map(lambda a: a.astype(jnp.float32),
                         system.participants[rx.name].params),
        smoke.prepare(system, rx.name,
                      next(r for r in requests if r.name == name)).prompt[0],
        None, toks, 128)
    worst = np.asarray(ref).argmin(-1).astype(np.int32)  # least rated
    bad = dict(done, **{name: dict(done[name], tokens=worst)})
    with pytest.raises(AssertionError, match=f"{name}: the engine's token"):
        smoke.check_reference(system, eng, rx.name, requests, bad)


def test_smoke_check_served_rejects_missing_mechanism(smoke, served):
    _, _, _, requests, done, eng = served
    stats = dict(eng.stats, radix_hits=0)
    with pytest.raises(AssertionError, match="radix_hits"):
        smoke.check_served(requests, done, stats)
    short = dict(done, long=dict(done["long"], tokens=np.zeros(1, np.int32)))
    with pytest.raises(AssertionError, match="'long'"):
        smoke.check_served(requests, short, eng.stats)


def test_smoke_main_refuses_a_cpu(smoke, capsys):
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(SystemExit) as ei:
        smoke.main([])
    assert ei.value.code != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_smoke_reference_tolerance_sees_a_wrong_input(smoke, served):
    """The tolerance is tight enough to catch a real fault: the same tokens
    at the wrong positions make the served logits miss the reference."""
    system, rx, _, requests, done, eng = served
    rxp = system.participants[rx.name]
    prompt, toks = requests[0].prompt, done[requests[0].name]["tokens"]
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), rxp.params)
    ref_last = smoke.reference_logits(rx, p32, prompt, None, toks, 128)[0]
    last, _ = smoke.served_logits(eng, rxp.params, prompt, None, int(toks[0]))
    assert smoke._rel_err(last, ref_last) <= smoke.REL_TOL
    wrong = np.concatenate([prompt[1:], prompt[:1]])  # every position moved
    last_w, _ = smoke.served_logits(eng, rxp.params, wrong, None,
                                    int(toks[0]))
    assert smoke._rel_err(last_w, ref_last) > smoke.REL_TOL
