"""The benchmark harness (``bench/``) on the CPU: discovery of its pieces by
name, the traffic generator, the window's measures, the trace reduction,
the FLOP and byte counts, and the check against the plain reference at tiny
widths, with the control and faults planted in the served path."""
import copy
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.harness import measures, trace as tr  # noqa: E402
from bench.harness.cell import NoChip, run_cell  # noqa: E402
from bench.harness.serve import Step, Window  # noqa: E402
from bench.harness.spec import Spec  # noqa: E402
from bench.harness.traffic import (Mix, Request, class_counts,  # noqa: E402
                                   exponential_gaps, lognormal_quantiles,
                                   zipf_quantiles)
from bench.harness.work import (paged_decode_need,  # noqa: E402
                                ragged_prefill_need)

HERE = os.path.dirname(os.path.abspath(__file__))

# tiny widths: the same two model families, the same engine features
TINY_RX = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=3,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               vocab_size=512)
TINY_TX = dict(hidden_size=48, intermediate_size=96, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, vocab_size=512)
TINY_ENGINE = dict(max_slots=4, max_seq=256, max_prefix=64,
                   prefill_token_budget=64, num_pages=96)
#: between what the served path reads at these widths (0 to about 0.02)
#: and what the fp8 control reads (about 0.25)
TINY_LIMIT = 0.1


def _dims(hf):
    """The counts of one model, by its architecture's reference side."""
    return Spec(ROOT).architecture(hf, "ref").dims(hf)


def _tiny_root(dst: str) -> str:
    """A checkout-like directory: the real BENCHMARK.json, metrics,
    architectures and traffic shapes, with a tiny configuration and tiny
    lengths."""
    os.makedirs(os.path.join(dst, "bench", "configs"))
    os.makedirs(os.path.join(dst, "bench", "traffic"))
    for piece in ("metrics", "arch", "ref"):
        shutil.copytree(os.path.join(ROOT, "bench", piece),
                        os.path.join(dst, "bench", piece))
    bench = Spec(ROOT).bench
    conf = Spec(ROOT).config(bench["configs"][0]["name"])
    conf["receiver"].update(TINY_RX)
    conf["transmitter"].update(TINY_TX)
    conf["engine"].update(TINY_ENGINE)
    conf["check"]["worst_gap_rms"] = TINY_LIMIT
    with open(os.path.join(dst, "bench", "configs", "tiny.json"), "w") as f:
        json.dump(conf, f)
    for w in bench["workloads"]:
        t = Spec(ROOT).traffic(w["traffic"])
        for c in t["classes"]:
            c["prompt"].update(median=20, min=8, max=48)
            c["output"].update(median=5, min=3, max=8)
            if "shared_prefix" in c:
                c["shared_prefix"].update(count=3, tokens=32)
        t["runup_s"] = 1.0
        if t["loop"] == "open":
            t.update(rate_per_s=4.0)
        else:
            t.update(clients=3, block=4)
        with open(os.path.join(dst, "bench", "traffic",
                               f"{w['traffic']}.json"), "w") as f:
            json.dump(t, f)
        w["config"] = "tiny"
    bench["configs"] = [{"name": "tiny", "source": "tiny widths",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "CPU tests"}]
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dst


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return Spec(_tiny_root(str(tmp_path_factory.mktemp("tiny"))))


# ----------------------------------------------------------------- discovery


def test_every_piece_is_found_by_name():
    spec = Spec(ROOT)
    for w in spec.bench["workloads"]:
        cell = spec.cell(w["name"])
        assert cell.config["receiver"]["hidden_size"] > 0
        assert cell.traffic["loop"] in ("open", "closed")
        names = {m.name for m in cell.metrics}
        assert "setup_s" in names
        for m in cell.metrics:
            assert callable(spec.reader(m.name))
    listed = {m["name"] for m in spec.bench["end_to_end"]
              + spec.bench["per_layer"]}
    reported = {m.name for w in spec.bench["workloads"]
                for m in spec.metrics_of(w["name"])}
    assert listed == reported


def test_a_new_config_mix_and_metric_need_only_new_files(tmp_path):
    root = _tiny_root(str(tmp_path / "r"))
    with open(os.path.join(root, "bench", "traffic", "burst.json"), "w") as f:
        json.dump({"loop": "closed", "clients": 2, "block": 2, "classes": [
            {"name": "x", "share": 1.0, "protocol": "standalone",
             "prompt": {"median": 9, "sigma": 0.1, "min": 8, "max": 10},
             "output": {"median": 3, "sigma": 0.1, "min": 3, "max": 3}}]},
            f)
    with open(os.path.join(root, "bench", "metrics", "answers.py"),
              "w") as f:
        f.write("def read(run):\n    return 42.0\n")
    shutil.copy(os.path.join(root, "bench", "configs", "tiny.json"),
                os.path.join(root, "bench", "configs", "tiny2.json"))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append(dict(bench["configs"][0], name="tiny2",
                                 file="bench/configs/tiny2.json"))
    bench["workloads"].append({"name": "burst", "config": "tiny2",
                               "traffic": "burst", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "answers", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "runtime", "moves": "setup_s",
                               "workloads": ["burst"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    cell = Spec(root).cell("burst")
    assert cell.traffic["clients"] == 2
    assert cell.config_name == "tiny2"
    assert [m.name for m in cell.metrics if not m.end_to_end] == ["answers"]
    assert Spec(root).reader("answers")(None) == 42.0


# ------------------------------------------------------------------ traffic


def test_lengths_keep_their_stated_parameters():
    spec = {"median": 160, "sigma": 0.8, "min": 16, "max": 1024}
    x = lognormal_quantiles(spec, 64)
    assert x.min() >= 16 and x.max() <= 1024
    assert np.median(x) == pytest.approx(160, rel=0.05)
    assert np.all(np.diff(x) >= 0)
    ranks = zipf_quantiles(16, 1.1, 64)
    counts = np.bincount(ranks, minlength=16)
    assert counts[0] > counts[1] > counts[5] and counts.sum() == 64
    # a fixed number of distinct lengths, each taken evenly
    y = lognormal_quantiles(dict(spec, levels=12), 64)
    assert len(set(y.tolist())) == 12 and len(y) == 64
    assert set(y.tolist()) == set(lognormal_quantiles(spec, 12).tolist())
    assert max(np.bincount(np.unique(y, return_inverse=True)[1])) <= 6
    gaps = exponential_gaps(0.5, 80)
    assert gaps.mean() == pytest.approx(2.0)
    assert class_counts([{"share": 0.8}, {"share": 0.2}], 80) == [64, 16]


def test_the_generator_is_deterministic_per_seed_and_fixes_the_work():
    t = Spec(ROOT).traffic("chat-mix")
    runup, seconds = t["runup_s"], 51.0
    a = Mix(t, 151936, 2**33 + 1).schedule(runup, seconds)
    b = Mix(t, 151936, 2**33 + 1).schedule(runup, seconds)
    c = Mix(t, 151936, 7).schedule(runup, seconds)
    assert all(np.array_equal(x.prompt, y.prompt) and x.steps == y.steps
               and x.due == y.due for x, y in zip(a, b))
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    # the same arrival times for every seed; each segment one set of sizes
    assert [r.due for r in a] == [r.due for r in c]
    n_win = round(t["rate_per_s"] * seconds)
    n_run = round(t["rate_per_s"] * runup)
    assert len(a) == n_run + n_win and a[-1].due < runup + seconds
    assert a[n_run - 1].due < runup == a[n_run].due
    for seg in (slice(0, n_run), slice(n_run, None)):
        assert sorted((r.prompt_len, r.klass) for r in a[seg]) == \
            sorted((r.prompt_len, r.klass) for r in c[seg])
        assert sorted(r.prefix for r in a[seg]) == \
            sorted(r.prefix for r in c[seg])
        assert sorted(r.steps for r in a[seg]) == \
            sorted(r.steps for r in c[seg])
    assert [r.klass for r in a[n_run:]] != [r.klass for r in c[n_run:]]
    chat = [r for r in a[n_run:] if r.klass == "chat"]
    assert len(chat) == round(0.8 * n_win)
    assert all(r.prompt_len >= 512 + 16 for r in chat)
    pre = Mix(t, 151936, 2**33 + 1).prefixes["chat"]
    assert all(np.array_equal(r.prompt[:512], pre[r.prefix]) for r in chat)
    warm = Mix(t, 151936, 2**33 + 1, stream=1).planned(seconds)
    assert sorted(r.prompt_len for r in warm) == \
        sorted(r.prompt_len for r in a)
    # the closed loop: blocks of the same sizes, in another order
    t = Spec(ROOT).traffic("c2c-closed")
    a = Mix(t, 151936, 2**33 + 1).take(2 * t["block"])
    c = Mix(t, 151936, 7).take(2 * t["block"])
    for blk in (slice(0, t["block"]), slice(t["block"], None)):
        assert sorted(r.prompt_len for r in a[blk]) == \
            sorted(r.prompt_len for r in c[blk])
        assert sorted(r.steps for r in a[blk]) == \
            sorted(r.steps for r in c[blk])
    assert Mix(t, 151936, 7, stream=1).planned(seconds)[0].due == 0.0


def test_the_open_loop_window_holds_whole_segments_at_any_length():
    t = dict(Spec(ROOT).traffic("chat-mix"), rate_per_s=0.37)
    for runup, seconds in ((0.0, 10.0), (60.0, 51.0), (7.5, 33.3)):
        s = Mix(t, 151936, 11).schedule(runup, seconds)
        due = [r.due for r in s]
        assert due == sorted(due) and all(0 <= d < runup + seconds
                                          for d in due)
        win = [d for d in due if d >= runup]
        assert len(win) == round(0.37 * seconds)
        assert len(due) - len(win) == round(0.37 * runup)


# ------------------------------------------------------------------ measures


def _req(i, S, steps, due, ready=None, done=None, tokens=None):
    r = Request(index=i, klass="x", protocol="standalone",
                prompt=np.zeros(S, np.int32), steps=steps, due=0.0)
    r.due_t, r.submit_t0, r.submit_t1 = due, due, due
    r.rid = i
    r.ready_step = ready
    r.done_step = done
    return r


def test_ttft_censoring_gaps_and_tokens_over_the_window():
    # steps end at 1, 2, 3, 4 s; the window opens at 1.5 s and closes at 4.5
    steps = [Step(t - 1.0, t, {"decode_steps": 1, "prefill_tokens": 0})
             for t in (1.0, 2.0, 3.0, 4.0)]
    a = _req(0, 10, 3, due=0.5, ready=0, done=1)  # run-up: ready at 1.0
    b = _req(1, 10, 5, due=1.6, ready=2)  # active at the close
    c = _req(2, 10, 4, due=2.5)  # no first token by the close
    for r, k in ((a, a.ready_step), (b, b.ready_step)):
        r.ready_t = steps[k].t1
    a.done_t = 2.0
    w = Window(1.5, 4.5, [a, b, c], steps, {}, {})
    assert w.k0 == 1
    assert measures.ttft_s(w) == pytest.approx([3.0 - 1.6, 4.5 - 2.5])
    # a's gap 1->2 ends in the window; b: 3->4, then its open gap 4->4.5
    assert sorted(measures.itl_s(w)) == pytest.approx([0.5, 1.0, 1.0])
    # a: one token at step 1 (its last); b: 2 at step 2, 1 at step 3
    assert measures.tokens(w) == 1 + 3
    assert measures.live_keys(b, 3) == 10 + 1 + 1


def test_prefill_chunks_follow_the_engine_budget_first_in_first_out():
    def window(chunks=(1, 2, 1), ready=(1, 2), matched=0):
        a = _req(0, 100, 4, 0.0, ready=ready[0])
        b = _req(1, 50, 4, 0.1, ready=ready[1])
        b.submit_t0 = 0.1
        steps = [Step(0, 1, {"decode_steps": 0, "prefill_tokens": n,
                             "prefill_chunks": c,
                             "radix_matched_tokens": matched})
                 for n, c in zip((64, 64, 22), chunks)]
        return Window(0.0, 3.0, [b, a], steps, {}, {})

    got = [(k, r.index, s, n)
           for k, r, s, n in measures.prefill_chunks(window())]
    assert got == [(0, 0, 0, 64), (1, 0, 64, 36), (1, 1, 0, 28),
                   (2, 1, 28, 22)]
    # where the engine's own counters disagree, the chunks are not told
    assert measures.prefill_chunks(window(chunks=(1, 1, 1))) is None
    assert measures.prefill_chunks(window(ready=(2, 2))) is None
    assert measures.prefill_chunks(window(matched=16)) is None


def test_served_flops_count_each_prompt_whole_however_chunked():
    from types import SimpleNamespace

    conf = Spec(ROOT).config("qwen3-0.6b_qwen2.5-0.5b")
    rx, tx = _dims(conf["receiver"]), _dims(conf["transmitter"])

    def flops(chunks, matched=0):
        a = _req(0, 100, 4, 0.0, ready=len(chunks) - 1)
        a.ready_t = float(len(chunks))
        steps = [Step(k, k + 1.0, {"prefill_tokens": n, "prefill_chunks": 1,
                                   "radix_matched_tokens": 0,
                                   "decode_steps": int(k == len(chunks) - 1)})
                 for k, n in enumerate(chunks)]
        w = Window(0.0, float(len(chunks)), [a], steps,
                   {"radix_matched_tokens": 0},
                   {"radix_matched_tokens": matched})
        return measures.served_flops(SimpleNamespace(window=w, rx=rx, tx=tx))

    # the prompt's causal prefill with its first logits, then one decode
    # over its 100 keys and the token the activation step wrote
    want = rx.prefill_flops(0, 100) + rx.head_flops() \
        + rx.decode_token_flops(101)
    assert flops([100]) == pytest.approx(want)
    assert flops([64, 36]) == pytest.approx(want)
    assert flops([30, 30, 40]) == pytest.approx(want)
    assert flops([100], matched=16) is None


# ---------------------------------------------------------------- the trace


def test_the_trace_reduction_on_a_recorded_fixture():
    with open(os.path.join(HERE, "trace_fixture.json")) as f:
        fx = json.load(f)
    got = tr.reduce(fx["events"])
    want = fx["expect"]
    assert got["window_s"] == pytest.approx(want["window_s"])
    # the fixture's expectation comes from a 10 ns timeline of its events
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-4)
    for prog, n in want["module_calls"].items():
        assert len(got["modules"][prog]) == n
    for key, (calls, secs) in want["kernels"].items():
        assert got["kernels"][key][0] == calls
        assert got["kernels"][key][1] == pytest.approx(secs)
    assert [g[0] for g in got["idle_gaps"][:len(want["gap_owners"])]] == \
        want["gap_owners"]
    assert all(not n.startswith("while") for n, _ in got["device_ops"])


def test_op_names():
    assert tr.op_name("%paged_decode_attention_pallas.11 = (bf16[32]) "
                      "custom-call(...)") == "paged_decode_attention_pallas"
    assert tr.op_name("jit_decode(12197187751717847029)") == "jit_decode"
    assert tr.op_name("%broadcast.306.clone2 = f32[8]") == "broadcast"


# ------------------------------------------------------------- work counts


def test_flop_and_byte_counts_against_hand_counts():
    rx = _dims(Spec(ROOT).config("qwen3-0.6b_qwen2.5-0.5b")["receiver"])
    # q 1024x2048, k and v 1024x1024, o 2048x1024, SwiGLU 3 x 1024x3072:
    # 15,728,640 weights a layer, two FLOPs each
    assert rx.dense_flops() == 2 * 15_728_640
    assert rx.head_flops() == 2 * 1024 * 151_936
    # one slot, 17 keys: two 16-token pages of k and v, q in, o, m, l out
    f, b = paged_decode_need(rx, 17, 16, 2)
    assert f == 4 * 16 * 128 * 17
    assert b == 32 * 2 * 8 * 128 * 2 + 2 * 16 * 128 * 2 + 2 * 16 * 4
    # rows 16..19 attend 17, 18, 19, 20 keys
    f, b = ragged_prefill_need(rx, 16, 4, 16, 2)
    assert f == 4 * 16 * 128 * (17 + 18 + 19 + 20)
    assert b == 32 * 2 * 8 * 128 * 2 + 4 * (2 * 16 * 128 * 2 + 2 * 16 * 4)
    assert rx.prefill_flops(16, 4) == 28 * (4 * rx.dense_flops()
                                            + 4 * 16 * 128 * 74)


# ------------------------------------------- the check, at tiny widths


def _run(spec, hook=None, seed=3, cell="chat-mix", control=False):
    return run_cell(spec, cell, seed, 4.0, False, t_start=0.0,
                    require_chip=False, control=control,
                    engine_hook=hook, log=lambda *a: None)


@pytest.fixture(scope="module")
def clean(tiny):
    return _run(tiny)


def test_the_served_path_agrees_with_the_reference_and_the_control_not(
        clean, tiny):
    chk = clean["check"]
    assert clean["correct"], chk
    assert chk["requests_checked"]["value"] >= 3
    assert chk["worst_gap_rms"]["value"] <= TINY_LIMIT
    assert chk["failed_requests"]["value"] == 0
    assert list(clean)[-1] == "check"
    assert {"itl_p95_ms", "setup_s"} == set(clean["metrics"])
    # the control in the served path's place: fp8 operands, whose first
    # choices trail the best, fail the same check
    ctl = _run(tiny, control=True)
    assert not ctl["correct"], ctl["check"]
    assert ctl["served_correct"]
    assert ctl["check"]["worst_gap_rms"]["value"] > 2 * TINY_LIMIT
    assert ctl["check"]["served_worst_gap_rms"]["value"] <= TINY_LIMIT
    assert list(ctl)[-1] == "check"


def _decode_fault(fault):
    def hook(eng):
        inner = eng._make_decode()

        def broken(params, table, tok, fused, rows, active):
            nxt, new = inner(params, table, tok, fused, rows, active)
            if fault == "token":  # altered where it is produced
                return (nxt + 1) % eng.cfg.vocab_size, new
            if fault == "state":  # the step returns its state unchanged
                return nxt, table
            # half of the batch left out: odd slots keep their last token
            odd = jnp.arange(nxt.shape[0]) % 2 == 1
            return jnp.where(odd, tok, nxt), new

        eng._decode = jax.jit(broken)
    return hook


@pytest.mark.parametrize("fault", ["token", "state", "half_batch"])
def test_a_fault_in_the_served_path_makes_the_run_incorrect(tiny, fault):
    res = _run(tiny, _decode_fault(fault))
    assert not res["correct"], res["check"]


def test_a_wrong_page_makes_the_run_incorrect(tiny, monkeypatch):
    from repro.models.cache import SlotTable

    write = SlotTable.write_token

    def shifted(pool, tok, page_map, pos, page_size):
        return write(pool, tok, (page_map + 1) % pool.shape[0], pos,
                     page_size)

    monkeypatch.setattr(SlotTable, "write_token", staticmethod(shifted))
    res = _run(tiny, lambda eng: None)
    assert not res["correct"], res["check"]


def test_a_failed_submit_makes_the_run_incorrect(tiny):
    # the window's first request fails at submit (as an exhausted pool
    # would); the loop serves on, the check fails the run
    cell = tiny.cell("chat-mix")
    target = Mix(cell.traffic, cell.config["receiver"]["vocab_size"], 3
                 ).schedule(cell.traffic["runup_s"], 4.0)[0].prompt.tolist()

    def hook(eng):
        submit = eng.submit

        def failing(prompt, *a, **kw):
            if np.asarray(prompt).ravel().tolist() == target:
                raise RuntimeError("RESOURCE_EXHAUSTED: planted")
            return submit(prompt, *a, **kw)

        eng.submit = failing

    res = _run(tiny, hook)
    assert not res["correct"], res["check"]
    assert res["check"]["failed_requests"]["value"] == 1


def test_the_closed_loop_cell_runs_and_reports_its_metrics(tiny):
    res = _run(tiny, lambda eng: None, cell="c2c-closed")
    assert res["correct"], res["check"]
    assert {"ttft_p50_ms", "itl_p95_ms", "tokens_per_s", "setup_s"} == \
        set(res["metrics"])


# ----------------------------------------------------- where it may not run


def test_no_chip_no_result(tiny):
    with pytest.raises(NoChip):
        run_cell(tiny, "chat-mix", 1, 1.0, False, t_start=0.0)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "chat-mix", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=240)
    assert p.returncode == 3 and p.stdout == ""


def test_the_benchmark_files_alone_do_not_run(tmp_path):
    dst = tmp_path / "bare"
    dst.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    for path in Spec(ROOT).bench["paths"]:
        shutil.copytree(os.path.join(ROOT, path), dst / path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "chat-mix", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=dst, env=env,
                       capture_output=True, text=True, timeout=240)
    assert p.returncode != 0 and p.stdout == ""


def test_tiny_copies_leave_the_real_files_alone():
    # _tiny_root works on copies: the committed configuration keeps its sizes
    conf = Spec(ROOT).config("qwen3-0.6b_qwen2.5-0.5b")
    assert conf["receiver"]["hidden_size"] == 1024
    assert copy.deepcopy(conf) == conf
