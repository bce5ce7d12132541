"""The reduction of the program's own spans (``bench/harness/spans.py``) on a
slice of a recorded chip trace: self times, device-idle time given to the
innermost host event, compiles given to the innermost program span, and the
per-layer readings taken from them."""
import json
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.harness import spans  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def fixture():
    with open(os.path.join(HERE, "span_fixture.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def program(fixture):
    return spans.reduce(fixture["events"])


def test_the_fixture_holds_every_program_span(program):
    assert {"fr.submit", "fr.prepare", "fr.tx_prefill", "fr.tx_export",
            "fr.wire", "fr.fuse", "fr.enqueue", "fr.pad", "fr.digest",
            "fr.fetch", "fr.hash", "fr.step", "fr.admit", "fr.decode",
            "fr.sync", "fr.retire"} <= set(program["spans"])


@pytest.mark.parametrize("key", ["count", "total_s", "self_s", "idle_s",
                                 "idle_in_s", "compiles", "compiles_in"])
def test_the_span_reduction_on_a_recorded_fixture(fixture, program, key):
    # the expectation comes from a 10 ns timeline of the fixture's events
    want = fixture["expect"]["spans"]
    assert set(program["spans"]) == set(want)
    for name, s in want.items():
        assert program["spans"][name][key] == pytest.approx(
            s[key], rel=1e-3, abs=2e-7), name


def test_idle_time_is_given_out_once(fixture, program):
    exp = fixture["expect"]
    assert program["window_s"] == pytest.approx(exp["window_s"])
    assert program["idle_other_s"] == pytest.approx(
        exp["idle_other_s"], rel=1e-3, abs=2e-7)
    # every idle nanosecond goes to one innermost event or to none
    given = sum(s["idle_s"] for s in program["spans"].values()) \
        + program["idle_other_s"]
    assert given == pytest.approx(exp["idle_s"], rel=1e-3)
    # a parent's idle time holds its children's
    sp = program["spans"]
    assert sp["fr.submit"]["idle_in_s"] >= sp["fr.prepare"]["idle_in_s"] \
        + sp["fr.enqueue"]["idle_in_s"] - 1e-9


def test_the_readings_on_the_fixture(fixture, program):
    exp = fixture["expect"]
    run = SimpleNamespace(
        trace={"program": program},
        window=SimpleNamespace(stats0=exp["stats0"], stats1=exp["stats1"]))
    got = {name: read(run) for name, read in spans.READINGS.items()}
    assert got == pytest.approx(exp["readings"], rel=1e-3)


def test_the_readings_find_nothing_without_the_program_spans(fixture):
    events = [e for e in fixture["events"] if not e["name"].startswith("fr.")]
    run = SimpleNamespace(
        trace={"program": spans.reduce(events)},
        window=SimpleNamespace(stats0={"decode_steps": 0},
                               stats1={"decode_steps": 3}))
    assert {n: r(run) for n, r in spans.READINGS.items()} == \
        dict.fromkeys(spans.READINGS)
    untraced = SimpleNamespace(trace=None, window=run.window)
    assert {n: r(untraced) for n, r in spans.READINGS.items()} == \
        dict.fromkeys(spans.READINGS)


def test_one_log_line_per_span_name(program):
    lines = spans.describe(program)
    assert len(lines) == len(program["spans"])
    assert lines[0].startswith("span fr.submit: 1 x")
