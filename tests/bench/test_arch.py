"""Model architectures as pieces of the benchmark found by name: the dense
Qwen files reproduce what the harness read before they existed
(``arch_fixture.json``), a new ``model_type`` enters a checkout as files
alone and runs its cell against its own reference, an untied output head is
read where the weights hold it, and the reference's side imports nothing of
the program."""
import dataclasses
import glob
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.harness import model, work  # noqa: E402
from bench.harness import reference as R  # noqa: E402
from bench.harness.reference import Reference  # noqa: E402
from bench.harness.spec import Spec  # noqa: E402
from test_bench_harness import TINY_RX, TINY_TX, _run, _tiny_root  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))

with open(os.path.join(HERE, "arch_fixture.json")) as f:
    FIXTURE = json.load(f)


def _sides(spec, conf, side):
    return [spec.architecture(conf[s], side) for s in model.SIDES]


# ------------------------------------- the same numbers as before the split


@pytest.fixture(scope="module", params=sorted(FIXTURE["configs"]))
def published(request):
    spec = Spec(ROOT)
    return request.param, spec, spec.config(request.param)


def test_the_program_configs_are_unchanged(published):
    name, spec, conf = published
    got = model.configs(conf, _sides(spec, conf, "arch"))
    want = FIXTURE["configs"][name]["program"]
    for cfg, side in zip(got, model.SIDES):
        assert json.loads(json.dumps(dataclasses.asdict(cfg))) == want[side]


def test_every_count_is_unchanged(published):
    name, spec, conf = published
    g = FIXTURE["grid"]
    want = FIXTURE["configs"][name]["counts"]
    rx, tx = (a.dims(conf[s]) for a, s in
              zip(_sides(spec, conf, "ref"), model.SIDES))
    for side, d in (("receiver", rx), ("transmitter", tx)):
        w = want[side]
        assert {f: getattr(d, f) for f in w["fields"]} == w["fields"]
        assert d.dense_flops() == w["dense_flops"]
        assert d.head_flops() == w["head_flops"]
        assert [d.attn_flops(k) for k in g["keys"]] == w["attn_flops"]
        assert [[d.decode_token_flops(k, p) for p in g["prefix"]]
                for k in g["keys"]] == w["decode_token_flops"]
        assert [[[d.prefill_flops(s, r, p) for p in g["prefix"]]
                 for r in g["rows"]] for s in g["start"]] == \
            w["prefill_flops"]
        assert [d.kv_row_bytes(i) for i in g["itemsize"]] == \
            w["kv_row_bytes"]
    assert [work.fuser_flops(tx, rx, t) for t in g["tokens"]] == \
        want["fuser_flops"]
    assert [work.transmit_flops(tx, rx, t) for t in g["tokens"]] == \
        want["transmit_flops"]
    assert [[list(work.paged_decode_need(rx, k, ps, i))
             for i in g["itemsize"]]
            for k in g["keys"] for ps in g["page_size"]] == \
        want["paged_decode_need"]
    assert [[[list(work.ragged_prefill_need(rx, s, r, ps, i))
              for i in g["itemsize"]] for r in g["rows"]]
            for s in g["start"] for ps in g["page_size"]] == \
        want["ragged_prefill_need"]


@pytest.fixture(scope="module")
def tiny_weights(published):
    name, spec, conf = published
    conf = json.loads(json.dumps(conf))
    conf["receiver"].update(TINY_RX)
    conf["transmitter"].update(TINY_TX)
    archs = _sides(spec, conf, "arch")
    rx, tx = model.configs(conf, archs)
    w = model.make_weights(rx, tx, FIXTURE["seed"], jnp.bfloat16,
                           rules=[a.WEIGHT_RULES for a in archs])
    return name, spec, conf, w


def test_the_weights_are_unchanged(tiny_weights):
    name, _, _, w = tiny_weights
    got = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(w)[0]:
        a = np.asarray(leaf, np.float64)
        got[model._path_name(path)] = [str(leaf.dtype), list(leaf.shape),
                                       float(a.sum()), float((a * a).sum())]
    assert got == FIXTURE["configs"][name]["tiny_weights"]


def test_the_reference_gaps_are_unchanged(tiny_weights):
    name, spec, conf, w = tiny_weights
    ref = Reference(conf, w, 64, 48, _sides(spec, conf, "ref"))
    for kind, want in FIXTURE["configs"][name]["tiny_gaps"].items():
        g = ref.gaps(np.asarray(want["prompt"], np.int32),
                     np.asarray(want["served"], np.int32), kind == "c2c",
                     control=True)
        assert set(g) == set(want["gaps"])
        for k, v in want["gaps"].items():
            np.testing.assert_allclose(g[k], v, rtol=0, atol=1e-6)


# --------------------------------- a new architecture is files and entries

#: A routed mixture-of-experts Qwen3 layer, as the program's ``moe`` family
#: serves it: softmax over the experts, the top k renormalised, each a SwiGLU
#: network. Every token takes all four experts (k = 4), so no near-tie in
#: the routing can part the bf16 served path from the float32 reference.
TOY_HF = dict(model_type="toy_moe", moe_intermediate_size=32, num_experts=4,
              num_experts_per_tok=4, num_hidden_layers=2)

TOY_ARCH = '''
from repro.configs.base import ModelConfig


def _fan_in(z, shape):
    return z * shape[-2] ** -0.5


WEIGHT_RULES = {"router": _fan_in, "w_gate": _fan_in, "w_up": _fan_in,
                "w_down": _fan_in}


def program_config(name, hf):
    heads = hf["num_attention_heads"]
    return ModelConfig(
        name=name, family="moe", num_layers=hf["num_hidden_layers"],
        d_model=hf["hidden_size"], num_heads=heads,
        num_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
        d_ff=0, moe_d_ff=hf["moe_intermediate_size"],
        num_experts=hf["num_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        vocab_size=hf["vocab_size"], qk_norm=bool(hf["qk_norm"]),
        rope_theta=float(hf["rope_theta"]),
        tie_embeddings=bool(hf["tie_word_embeddings"]),
        norm_eps=float(hf["rms_norm_eps"]), source=hf["source"])
'''

TOY_REF = '''
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from bench.harness.reference import mm, rms_norm
from bench.ref.qwen3 import Dims


def block(hf, lp, x, attend, lower):
    eps = hf["rms_norm_eps"]
    o, kv = attend(lp["attn"], rms_norm(x, lp["norm1"]["scale"], eps))
    x = x + o
    f = lp["ffn"]
    h = rms_norm(x, lp["norm2"]["scale"], eps)
    p = jax.nn.softmax(mm(h, f["router"], lower), axis=-1)
    top, idx = jax.lax.top_k(p, hf["num_experts_per_tok"])
    top = top / top.sum(-1, keepdims=True)
    share = (jax.nn.one_hot(idx, hf["num_experts"]) * top[..., None]).sum(1)
    g = jax.nn.silu(mm(h, f["w_gate"], lower))  # (E, W, f)
    y = mm(g * mm(h, f["w_up"], lower), f["w_down"], lower)  # (E, W, d)
    return x + jnp.einsum("we,ewd->wd", share, y), kv


@dataclass(frozen=True)
class MoeDims(Dims):
    experts: int = 0

    def dense_flops(self):
        return super().dense_flops() + 2 * self.d * self.experts


def dims(hf):
    h = hf["num_attention_heads"]
    return MoeDims(hf["num_hidden_layers"], hf["hidden_size"], h,
                   hf["num_key_value_heads"], hf["head_dim"],
                   hf["moe_intermediate_size"] * hf["num_experts_per_tok"],
                   hf["vocab_size"], hf["num_experts"])
'''


def _add_cell(root, name, conf, files=(), metric=None):
    """Adds configuration ``name``, its files and a cell of the same name
    on the tiny chat-mix traffic to the checkout at ``root``: new files
    and new entries only."""
    for rel, text in files:
        with open(os.path.join(root, rel), "w") as f:
            f.write(text)
    with open(os.path.join(root, "bench", "configs", f"{name}.json"),
              "w") as f:
        json.dump(conf, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append(dict(bench["configs"][0], name=name,
                                 file=f"bench/configs/{name}.json"))
    bench["workloads"].append({"name": name, "config": name,
                               "traffic": "chat-mix", "chips": 1,
                               "why": "CPU test"})
    if metric:
        bench["end_to_end"].append({"name": metric, "unit": "flops",
                                    "better": "lower", "bound": 0.01,
                                    "source": "host_clock",
                                    "workloads": [name]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return Spec(root)


def _tiny_conf(root):
    return Spec(root).config("tiny")


def test_a_new_architecture_needs_only_new_files(tmp_path):
    root = _tiny_root(str(tmp_path / "r"))
    conf = _tiny_conf(root)
    conf["receiver"].update(TOY_HF)
    counted = "bench/metrics/toy_layer_flops.py"
    spec = _add_cell(root, "toy", conf, metric="toy_layer_flops", files=[
        ("bench/arch/toy_moe.py", TOY_ARCH), ("bench/ref/toy_moe.py", TOY_REF),
        (counted, "def read(run):\n    return run.rx.dense_flops()\n")])
    res = _run(spec, lambda eng: None, cell="toy")
    assert res["correct"], res["check"]
    assert res["check"]["requests_checked"]["value"] >= 3
    # the receiver's counts are the toy's: attention projections, four
    # routed experts of width 32 and the router
    d, hd = 64, 16
    want = 2 * d * (2 * 4 * hd + 2 * 2 * hd) + 2 * 3 * d * 32 * 4 + 2 * d * 4
    assert res["metrics"]["toy_layer_flops"]["value"] == want


@pytest.mark.parametrize("missing", ["arch", "ref"])
def test_a_missing_architecture_file_names_the_file(tmp_path, missing):
    root = _tiny_root(str(tmp_path / "r"))
    conf = _tiny_conf(root)
    conf["receiver"].update(TOY_HF)
    files = [(f"bench/{side}/toy_moe.py", text) for side, text in
             (("arch", TOY_ARCH), ("ref", TOY_REF)) if side != missing]
    spec = _add_cell(root, "toy", conf, files=files)
    want = os.path.join(root, "bench", missing, "toy_moe.py")
    with pytest.raises(FileNotFoundError, match="toy_moe") as e:
        _run(spec, lambda eng: None, cell="toy")
    assert want in str(e.value)


# ------------------------------------------------------ an untied output head


@pytest.mark.parametrize("head", ["lm_head", "embedding"])
def test_an_untied_head_is_read_where_the_weights_hold_it(
        tmp_path, monkeypatch, head):
    root = _tiny_root(str(tmp_path / "r"))
    conf = _tiny_conf(root)
    conf["receiver"]["tie_word_embeddings"] = False
    spec = _add_cell(root, "untied", conf)
    if head == "embedding":  # a reference that reads the wrong head
        monkeypatch.setattr(R, "head_table",
                            lambda hf, params: params["embed"]["table"])
    res = _run(spec, lambda eng: None, cell="untied")
    assert res["correct"] == (head == "lm_head"), res["check"]


# ----------------------------------- the reference stands apart from the program


def test_the_reference_side_imports_nothing_of_the_program():
    files = sorted(glob.glob(os.path.join(ROOT, "bench", "ref", "*.py")))
    assert files
    code = (
        "import sys\n"
        "import bench.harness.reference\n"
        "from bench.harness.spec import load_module\n"
        f"for path in {files!r}:\n"
        "    load_module(path, 'reference side')\n"
        "bad = sorted(m for m in sys.modules if m.split('.')[0] == 'repro')\n"
        "assert not bad, bad\n"
        "print('ok')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0 and p.stdout.strip() == "ok", p.stderr
