"""repro.analysis: linter rule corpus (true positive + no false positive per
rule), jit-root/reachability behaviour, suppression comments, the CLI, the
self-lint acceptance gate, and TraceGuard retrace enforcement on the engine.

Corpus contract (ISSUE 7): every rule class ships a known-bad snippet the
linter must flag and a known-good twin it must stay silent on — CI treats any
finding as a failure, so the no-FP half is what keeps the gate trustworthy.
"""
import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import RULES, TraceGuard, TraceGuardError, lint_paths
from repro.analysis.__main__ import main as lint_main
from repro.configs.base import ModelConfig
from repro.configs.case_study import tiny_zoo
from repro.core import fuser as F
from repro.launch.engine import ContinuousBatchingEngine
from repro.models import transformer as T

VOCAB = 64

_PALLAS_HEADER = """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]
"""

# rule -> (bad snippet, good twin). Bad must produce >= 1 finding of exactly
# that rule; good must produce zero findings of any rule.
CORPUS = {
    "tracer-branch": (
        """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            y = jnp.sum(x)
            if y > 0:
                return y
            return -y
        """,
        """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            y = jnp.sum(x)
            return jnp.where(y > 0, y, -y)

        @jax.jit
        def g(x):
            if x.shape[0] > 2:  # static shape: fine under jit
                return x * 2
            return x
        """,
    ),
    "tracer-bool-cast": (
        """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            s = jnp.sum(x)
            assert s > 0
            return bool(jnp.max(x) > 0), s
        """,
        """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x, lo):
            assert x.ndim == 2, x.shape  # static metadata: fine
            assert lo is not None       # identity test: fine
            return jnp.sum(x)
        """,
    ),
    "tracer-host-op": (
        """
        import jax
        import jax.numpy as jnp
        import numpy as np

        @jax.jit
        def f(x):
            y = jnp.sum(x)
            hi = np.asarray(y)
            return float(jnp.mean(x)), jnp.max(x).item(), hi
        """,
        """
        import jax
        import jax.numpy as jnp
        import numpy as np

        @jax.jit
        def f(x):
            n = int(x.shape[0])          # static shape
            host = np.arange(n)          # np on host values only
            return jnp.sum(x) + jnp.asarray(host)

        def host_side(x):
            return float(np.mean(x))     # not jit-reachable: fine
        """,
    ),
    "trace-side-effect": (
        """
        import jax
        import jax.numpy as jnp

        class Engine:
            def __init__(self):
                self.stats = {}
                self.fn = jax.jit(lambda x: self.step(x))

            def step(self, x):
                self.stats["steps"] = 1
                print("tracing step")
                return jnp.sum(x)
        """,
        """
        import jax
        import jax.numpy as jnp

        class Engine:
            def __init__(self):
                self.stats = {}
                self.fn = jax.jit(lambda x: self.step(x))

            def step(self, x):
                jax.debug.print("step {x}", x=x)  # runs per call, not per trace
                return jnp.sum(x)

            def host_update(self):  # not jit-reachable: fine
                self.stats["drained"] = 1
        """,
    ),
    "dropped-at-set": (
        """
        import jax.numpy as jnp

        def f(x):
            x.at[0].set(1.0)
            return x
        """,
        """
        import jax.numpy as jnp

        def f(x):
            x = x.at[0].set(1.0)
            return x
        """,
    ),
    "dict-kv-access": (
        """
        from repro.models.cache import FusedPrefix

        def f(obj):
            fp = FusedPrefix.ensure(obj)
            return fp["k"], fp["v"]
        """,
        """
        from repro.models.cache import FusedPrefix

        def f(obj, entry):
            fp = FusedPrefix.ensure(obj)
            return fp.k, fp.v, entry["k"]  # plain layer dicts stay dicts
        """,
    ),
    "dict-kv-literal": (
        """
        def f(k, v, b):
            return {"k": k, "v": v, "bias": b}
        """,
        """
        from repro.models.cache import FusedPrefix

        def f(k, v, b):
            typed = FusedPrefix(k=k, v=v, bias=b)
            layer_entry = {"k": k, "v": v}  # 2-key cache entries are fine
            return typed, layer_entry
        """,
    ),
    "pallas-grid-arity": (
        _PALLAS_HEADER + """
        def f(x):
            return pl.pallas_call(
                kernel,
                grid=(4, 4),
                in_specs=[pl.BlockSpec((8, 8), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 8), lambda i, j: (i, j)),
                out_shape=jax.ShapeDtypeStruct((32, 32), jnp.float32),
            )(x)
        """,
        _PALLAS_HEADER + """
        def f(x):
            grid = (4, 4)
            spec = pl.BlockSpec((8, 8), lambda i, j: (i, j))
            return pl.pallas_call(
                kernel,
                grid=grid,
                in_specs=[spec],
                out_specs=spec,
                out_shape=jax.ShapeDtypeStruct((32, 32), jnp.float32),
            )(x)
        """,
    ),
    "pallas-scalar-prefetch": (
        _PALLAS_HEADER + """
        def f(x, y):
            return pl.pallas_call(
                kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((8,), lambda i: (i,))],
                out_specs=pl.BlockSpec((8,), lambda i: (i,)),
                out_shape=jax.ShapeDtypeStruct((32,), jnp.float32),
            )(x, y)
        """,
        _PALLAS_HEADER + """
        def f(x, y):
            specs = [pl.BlockSpec((8,), lambda i: (i,))] * 2
            return pl.pallas_call(
                kernel,
                grid=(4,),
                in_specs=specs,
                out_specs=pl.BlockSpec((8,), lambda i: (i,)),
                out_shape=jax.ShapeDtypeStruct((32,), jnp.float32),
            )(x, y)
        """,
    ),
    "pallas-out-shape": (
        _PALLAS_HEADER + """
        def f(x):
            return pl.pallas_call(
                kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((8,), lambda i: (i,))],
                out_specs=[pl.BlockSpec((8,), lambda i: (i,)),
                           pl.BlockSpec((8,), lambda i: (i,))],
                out_shape=[jax.ShapeDtypeStruct((32,), jnp.float32)],
            )(x)
        """,
        _PALLAS_HEADER + """
        def f(x):
            return pl.pallas_call(
                kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((8,), lambda i: (i,))],
                out_specs=[pl.BlockSpec((8,), lambda i: (i,)),
                           pl.BlockSpec((8,), lambda i: (i,))],
                out_shape=[jax.ShapeDtypeStruct((32,), jnp.float32),
                           jax.ShapeDtypeStruct((32,), jnp.int32)],
            )(x)
        """,
    ),
    "bare-assert-kernel": (
        """
        def tile(T, bt):
            assert T % bt == 0, (T, bt)
            return T // bt
        """,
        """
        def tile(T, bt):
            if T % bt != 0:
                raise ValueError(f"T {T} not divisible by block {bt}")
            return T // bt
        """,
    ),
    # ---- OWNxxx: page-lease ownership pass (repro.analysis.ownership) ----
    "lease-leak": (
        """
        def admit(allocator):
            lease = allocator.lease(fresh=2)   # never sunk: pages held forever
            return lease.num_pages
        """,
        """
        def admit(allocator, table, slot):
            lease = allocator.lease(fresh=2)
            row = lease.page_row(8, 99)
            table = table.insert_slot(slot, row)
            return table, lease

        def borrow(lease):
            return lease.num_pages  # parameters are borrowed, not owned
        """,
    ),
    "lease-double-release": (
        """
        def evict(allocator, lease0):
            lease = allocator.lease(fresh=1)
            allocator.release(lease)
            allocator.release(lease)
        """,
        """
        def evict(allocator, keep):
            lease = allocator.lease(fresh=1)
            if keep:
                allocator.release(lease)
            else:
                allocator.release(lease)  # exactly once on every path
        """,
    ),
    "lease-use-after-release": (
        """
        def evict(allocator):
            lease = allocator.lease(fresh=1)
            allocator.release(lease)
            return lease.ids()
        """,
        """
        def evict(allocator, index):
            lease = allocator.lease(fresh=1)
            index.register(lease.ids())  # derived views consumed pre-release
            n = lease.num_pages
            allocator.release(lease)
            return n                     # plain ints: not a tainted view
        """,
    ),
    "shared-write-no-cow": (
        """
        def admit(allocator, table, slot, cache, phys, off, pos):
            lease = allocator.lease(shared=[3, 4], fresh=1)
            row = lease.page_row(8, 99)
            table = table.insert_suffix(slot, cache, phys, off, row, pos)
            return table, lease
        """,
        """
        def admit(allocator, table, slot, cache, phys, off, pos):
            lease = allocator.lease(shared=[3, 4], fresh=1)
            allocator.cow(lease, 1)   # fault the partial page first
            row = lease.page_row(8, 99)
            table = table.insert_suffix(slot, cache, phys, off, row, pos)
            return table, lease
        """,
    ),
    "jit-page-mutation": (
        """
        import jax

        class Eng:
            def __init__(self):
                self._step = jax.jit(lambda t: self.decode(t))

            def decode(self, t):
                ids = self._allocator.alloc(1)  # host mutation under trace
                return t, ids
        """,
        """
        import jax

        class Eng:
            def __init__(self):
                self._step = jax.jit(lambda t: self.decode(t))

            def decode(self, t):
                return t * 2

            def admit(self):                  # host-side: mutation is fine
                ids = self._allocator.alloc(1)
                return ids
        """,
    ),
    "private-on-wire": (
        """
        from repro.core import transport as TR

        def ship(cfg, cache, wire: TR.IdentityChannel):
            stack = cache.export_stack(cfg, length=8)
            return wire.transmit(stack)
        """,
        """
        from repro.core import transport as TR

        def ship(cfg, cache, wire: TR.IdentityChannel):
            msg = TR.stack_message(cache.export_stack(cfg, length=8))
            received, nbytes = wire.transmit(msg)
            return received, nbytes
        """,
    ),
    "message-outside-codec": (
        """
        from repro.core import transport as TR

        def handcraft(ids):
            return TR.Message(tokens=ids)
        """,
        """
        from repro.core import transport as TR

        def handcraft(ids):
            return TR.token_message(ids)

        class MarkerChannel(TR.Channel):
            def encode(self, msg: TR.Message) -> TR.Message:
                # codec internals ARE the sanctioned place to build messages
                return TR.Message(tokens=msg.tokens,
                                  payload=dict(msg.payload))
        """,
    ),
    "unaccounted-wire-bytes": (
        """
        from repro.core.protocol import FederationProtocol, PreparedRequest

        class LeakyC2C(FederationProtocol):
            name = "leaky"

            def prepare(self, system, receiver, rx_ids, tx_names, *,
                        steps, key, gated=True, tx_prompts=None):
                stacks, _ = system.transmit_stacks(tx_names, {})
                fused = system.fused_prefix(receiver, tx_names, stacks)
                return PreparedRequest(prompt=rx_ids, fused=fused)
        """,
        """
        from repro.core.protocol import FederationProtocol, PreparedRequest

        class AccountedC2C(FederationProtocol):
            name = "accounted"

            def prepare(self, system, receiver, rx_ids, tx_names, *,
                        steps, key, gated=True, tx_prompts=None):
                stacks, wire_bytes = system.transmit_stacks(tx_names, {})
                fused = system.fused_prefix(receiver, tx_names, stacks)
                return PreparedRequest(prompt=rx_ids, fused=fused,
                                       transmitters=tx_names,
                                       wire_bytes=wire_bytes)
        """,
    ),
    "pipeline-drops-stage": (
        """
        from repro.core.protocol import WireSchema
        from repro.core.transport import (Pipeline, QuantChannel,
                                          RephraseChannel)

        SCHEMA = WireSchema(protocol="c2c", stages=("rephrase", "quant"))

        def build_wire(paraphraser, key):
            return Pipeline([RephraseChannel(paraphraser, key)])
        """,
        """
        from repro.core.protocol import WireSchema
        from repro.core.transport import (Pipeline, QuantChannel,
                                          RephraseChannel)

        SCHEMA = WireSchema(protocol="c2c", stages=("rephrase", "quant"))

        def build_wire(paraphraser, key):
            return Pipeline([RephraseChannel(paraphraser, key),
                             QuantChannel()])
        """,
    ),
    "jit-wire-sink": (
        """
        import jax
        from repro.core import transport as TR

        @jax.jit
        def step(x, wire: TR.IdentityChannel):
            msg = TR.token_message(x)
            return wire.encode(msg)
        """,
        """
        import jax
        import jax.numpy as jnp
        from repro.core import transport as TR

        @jax.jit
        def step(x):
            return jnp.sum(x)

        def host_transmit(x, wire: TR.IdentityChannel):
            msg = TR.token_message(step(x))
            received, nbytes = wire.transmit(msg)
            return received, nbytes
        """,
    ),
}


def _write(tmp_path, rule, kind, src):
    # PLC004 only fires inside kernel modules: route its corpus there
    sub = "kernels" if rule == "bare-assert-kernel" else "lib"
    d = tmp_path / kind / sub
    d.mkdir(parents=True, exist_ok=True)
    p = d / f"{rule.replace('-', '_')}.py"
    p.write_text(textwrap.dedent(src))
    return str(p)


@pytest.mark.parametrize("rule", sorted(CORPUS))
def test_rule_true_positive(tmp_path, rule):
    path = _write(tmp_path, rule, "bad", CORPUS[rule][0])
    hits = lint_paths([path])
    assert any(f.rule == rule for f in hits), (
        f"{rule}: known-bad snippet produced {[f.format() for f in hits]}")


@pytest.mark.parametrize("rule", sorted(CORPUS))
def test_rule_no_false_positive(tmp_path, rule):
    path = _write(tmp_path, rule, "good", CORPUS[rule][1])
    hits = lint_paths([path])
    assert hits == [], (
        f"{rule}: known-good snippet produced {[f.format() for f in hits]}")


def test_corpus_covers_at_least_eight_rules():
    assert len(CORPUS) >= 8
    assert set(CORPUS) <= set(RULES)


def test_suppression_comment_drops_finding(tmp_path):
    src = textwrap.dedent("""
        def f(k, v, b):
            # lint: allow(dict-kv-literal)
            a = {"k": k, "v": v, "bias": b}
            b2 = {"k": k, "v": v, "bias": b}  # lint: allow(dict-kv-literal)
            return a, b2
    """)
    p = tmp_path / "sup.py"
    p.write_text(src)
    assert lint_paths([str(p)]) == []
    # the same file without the comments does get flagged (twice)
    q = tmp_path / "nosup.py"
    q.write_text(src.replace("# lint: allow(dict-kv-literal)", ""))
    assert len(lint_paths([str(q)])) == 2


def test_audit_suppressions_flags_stale_and_unknown(tmp_path):
    """--audit-suppressions: an allow() whose rule no longer fires in its
    window is stale; an unknown rule name is always stale; a live one (the
    finding it covers still exists raw) is kept."""
    from repro.analysis import audit_suppressions

    src = textwrap.dedent("""
        def f(k, v, b):
            live = {"k": k, "v": v, "bias": b}  # lint: allow(dict-kv-literal)
            # lint: allow(dict-kv-literal)
            stale = [k, v, b]
            bogus = 1  # lint: allow(no-such-rule)
            return live, stale, bogus
    """)
    p = tmp_path / "sup.py"
    p.write_text(src)
    stale = audit_suppressions([str(p)])
    assert sorted(s.rule for s in stale) == ["dict-kv-literal",
                                             "no-such-rule"]
    assert all(s.path == str(p) for s in stale)
    # CLI surface: exit 1 + one line per stale comment
    assert lint_main([str(p), "--audit-suppressions"]) == 1
    clean = tmp_path / "clean.py"
    clean.write_text(src.splitlines()[1] + "\n    return k\n")
    assert lint_main([str(clean), "--audit-suppressions"]) == 0


def test_jit_factory_pattern_is_reachable(tmp_path):
    """jax.jit(self._make_step()) marks the factory's nested defs as roots."""
    src = textwrap.dedent("""
        import jax
        import jax.numpy as jnp

        class Eng:
            def __init__(self, params):
                self._step = jax.jit(self._make_step())

            def _make_step(self):
                def step(x):
                    y = jnp.sum(x)
                    if y > 0:
                        return y
                    return -y
                return step
    """)
    p = tmp_path / "factory.py"
    p.write_text(src)
    hits = lint_paths([str(p)])
    assert [f.rule for f in hits] == ["tracer-branch"]


def test_unreachable_code_is_not_tracer_checked(tmp_path):
    """The same tracer sin outside any jit-reachable graph stays silent —
    host-side orchestration code may branch on device values after a sync."""
    src = textwrap.dedent("""
        import jax.numpy as jnp

        def host_loop(x):
            y = jnp.sum(x)
            if y > 0:
                return y
            return -y
    """)
    p = tmp_path / "host.py"
    p.write_text(src)
    assert lint_paths([str(p)]) == []


def test_cli_json_and_exit_codes(tmp_path, capsys):
    bad = _write(tmp_path, "dict-kv-literal", "bad",
                 CORPUS["dict-kv-literal"][0])
    assert lint_main([bad, "--json"]) == 1
    report = capsys.readouterr().out
    assert '"dict-kv-literal"' in report and '"count": 1' in report
    good = _write(tmp_path, "dict-kv-literal", "good",
                  CORPUS["dict-kv-literal"][1])
    assert lint_main([good]) == 0


def test_cli_sarif_output(tmp_path, capsys):
    """--sarif: valid SARIF 2.1.0 skeleton, full rule catalogue, one result
    per finding with a physical location; exit codes match --json."""
    import json

    bad = _write(tmp_path, "private-on-wire", "bad",
                 CORPUS["private-on-wire"][0])
    assert lint_main([bad, "--sarif"]) == 1
    log = json.loads(capsys.readouterr().out)
    assert log["version"] == "2.1.0"
    run = log["runs"][0]
    declared = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert {RULES[n].code for n in RULES} <= declared
    results = run["results"]
    assert results and all(r["level"] == "error" for r in results)
    assert any(r["ruleId"] == "WIR001" for r in results)
    loc = results[0]["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith(".py")
    assert loc["region"]["startLine"] >= 1
    good = _write(tmp_path, "private-on-wire", "good",
                  CORPUS["private-on-wire"][1])
    assert lint_main([good, "--sarif"]) == 0
    clean = json.loads(capsys.readouterr().out)
    assert clean["runs"][0]["results"] == []


def test_self_lint_src_and_benchmarks_clean():
    """The acceptance gate: the repo's own src/, benchmarks/, examples/ and
    experiments/ trees lint clean — including the WIRxxx wire-contract pass
    (CI runs the same command as a job)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    findings = lint_paths([os.path.join(root, d)
                           for d in ("src", "benchmarks", "examples",
                                     "experiments")
                           if os.path.isdir(os.path.join(root, d))])
    assert findings == [], "\n".join(f.format() for f in findings)


def test_mypy_analysis_and_cache_clean():
    """The CI mypy gate, runnable locally when mypy is installed (hermetic
    environments without it skip — CI pins mypy in requirements.txt)."""
    pytest.importorskip("mypy")
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"))
    res = subprocess.run(
        [sys.executable, "-m", "mypy", "--config-file",
         os.path.join(root, "mypy.ini"),
         os.path.join(root, "src", "repro", "analysis"),
         os.path.join(root, "src", "repro", "models", "cache.py"),
         os.path.join(root, "src", "repro", "launch", "prefix_cache.py"),
         os.path.join(root, "src", "repro", "launch", "engine.py"),
         os.path.join(root, "src", "repro", "core", "transport.py"),
         os.path.join(root, "src", "repro", "core", "protocol.py"),
         os.path.join(root, "src", "repro", "core", "quant.py")],
        capture_output=True, text=True, env=env, cwd=root)
    assert res.returncode == 0, res.stdout + res.stderr


# ----------------------------------------------------------------- TraceGuard


def test_traceguard_shape_perturbation_trips_with_avals():
    @jax.jit
    def watched_fn(x):
        return x * 2

    with pytest.raises(TraceGuardError) as ei:
        with TraceGuard(max_traces={"watched_fn": 1}):
            watched_fn(jnp.zeros((4,)))
            watched_fn(jnp.zeros((4,)))      # cache hit: free
            watched_fn(jnp.zeros((8,)))      # retrace: must trip
    msg = str(ei.value)
    assert "watched_fn" in msg and "budget is 1" in msg
    assert "float32[8]" in msg       # the offending avals...
    assert "float32[4]" in msg       # ...and the previous trace's


def test_traceguard_exact_counts():
    @jax.jit
    def counted_fn(x):
        return x + 1

    with TraceGuard(exact={"counted_fn": 1}) as tg:
        for _ in range(4):
            counted_fn(jnp.ones((3,)))   # one trace, three cache hits
    assert tg.counts["counted_fn"] == 1

    with pytest.raises(TraceGuardError, match="expected exactly 1"):
        with TraceGuard(exact={"never_traced_fn": 1}):
            pass


def test_traceguard_restores_hook_after_exception():
    from jax._src.interpreters import partial_eval as pe

    before = pe.trace_to_jaxpr
    with pytest.raises(TraceGuardError):
        with TraceGuard(exact={"missing": 1}):
            pass
    assert pe.trace_to_jaxpr is before


def test_traceguard_fails_loudly_when_hook_is_blind(monkeypatch):
    """A hook on an entry point jit no longer calls must refuse to start,
    not report zero traces against every budget."""
    import types

    from repro.analysis import traceguard

    from jax._src.interpreters import partial_eval as pe

    blind = types.SimpleNamespace(trace_to_jaxpr=pe.trace_to_jaxpr)
    monkeypatch.setattr(traceguard, "_pe", blind)
    with pytest.raises(RuntimeError, match="cannot see jit traces"):
        with TraceGuard(max_traces={"decode": 1}):
            pass
    assert blind.trace_to_jaxpr is pe.trace_to_jaxpr  # hook removed again


# ------------------------------------------------- TraceGuard x engine


def _prompt(key, n):
    return jax.random.randint(key, (1, n), 0, VOCAB)


def test_traceguard_engine_mixed_protocols_decode_once():
    """The acceptance invariant, enforced by the guard rather than the
    engine's hand-maintained stats: decode traces exactly once across
    standalone, C2C and T2T requests over several waves with changing
    prompt lengths and request mixes."""
    from repro.core.fedrefine import FedRefineSystem, Participant

    zoo = tiny_zoo(vocab_size=VOCAB)
    key = jax.random.PRNGKey(50)
    members = [Participant(c.name, c,
                           T.init_params(c, jax.random.fold_in(key, i),
                                         jnp.float32))
               for i, c in enumerate([zoo["receiver"],
                                      zoo["transmitters"][0]])]
    system = FedRefineSystem.build(members)
    rx = members[0].name
    system.make_engine(rx, max_slots=3, max_seq=64, max_prefix=8)

    with TraceGuard(exact={"decode": 1}) as tg:
        for wave, n in enumerate((5, 7)):
            p = _prompt(jax.random.fold_in(key, 10 + wave), n)
            system.submit(rx, p, 3, protocol="standalone")
            system.submit(rx, p, 3, protocol="c2c")
            system.submit(rx, p, 3, protocol="t2t")
            out = system.drain(rx)
            assert all(len(r["tokens"]) == 3 for r in out.values())
    # the guard counted the actual jit lowerings — independent of stats
    assert tg.counts["decode"] == 1


def test_traceguard_suffix_prefill_once_per_bucket():
    """Shared-prefix admissions suffix-prefill through one trace per suffix
    bucket: tails of 4 and 6 tokens share the 8-bucket, a 12-token tail opens
    the 16-bucket — two sprefill traces total, decode still one."""
    cfg = ModelConfig(name="tg-tiny", family="dense", num_layers=2,
                      d_model=32, num_heads=2, num_kv_heads=1, head_dim=16,
                      d_ff=64, vocab_size=VOCAB, tie_embeddings=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    key = jax.random.PRNGKey(51)
    shared = _prompt(key, 16)
    prompts = [shared]
    for i, tail_len in enumerate((4, 6, 12)):
        tail = jax.random.randint(jax.random.fold_in(key, i + 1),
                                  (1, tail_len), 0, VOCAB, jnp.int32)
        prompts.append(jnp.concatenate([shared, tail], axis=1))

    eng = ContinuousBatchingEngine(cfg, params, max_slots=8, max_seq=64,
                                   paged=True, page_size=8, num_pages=32,
                                   prefix_cache=True, prompt_bucket=8)
    with TraceGuard(exact={"decode": 1, "sprefill": 2}) as tg:
        rids = [eng.submit(p, 4) for p in prompts]
        done = {c.rid: c.tokens for c in eng.drain()}
    assert set(done) == set(rids)
    assert eng.stats["radix_hits"] == 3
    assert tg.counts["decode"] == 1 and tg.counts["sprefill"] == 2


def test_traceguard_engine_bench_style_smoke():
    """What the engine_bench smoke runs under: a short mixed run inside a
    decode budget of one — and token outputs are unaffected by the guard."""
    rx_zoo = tiny_zoo(vocab_size=VOCAB)
    rx = rx_zoo["receiver"]
    tx = rx_zoo["transmitters"][0]
    key = jax.random.PRNGKey(52)
    p_rx = T.init_params(rx, key, jnp.float32)
    p_tx = T.init_params(tx, jax.random.fold_in(key, 1), jnp.float32)
    fz = F.init_fuser(tx, rx, jax.random.fold_in(key, 2))
    p = _prompt(key, 6)
    _, txc = T.prefill(tx, p_tx, p, max_seq=6, cache_dtype=jnp.float32)
    fused = F.project_cache(fz, tx, rx, txc.export_stack(tx, length=6))

    def run():
        eng = ContinuousBatchingEngine(rx, p_rx, max_slots=2, max_seq=40,
                                       max_prefix=8)
        ra = eng.submit(p, 5, fused=fused)
        rb = eng.submit(_prompt(jax.random.fold_in(key, 3), 4), 5)
        done = {c.rid: c.tokens for c in eng.drain()}
        return done[ra], done[rb]

    base = run()
    with TraceGuard(max_traces={"decode": 1}) as tg:
        guarded = run()
    assert tg.counts["decode"] == 1
    for a, b in zip(base, guarded):
        assert np.array_equal(a, b)


# ------------------------------------------- PageSanitizer (runtime checker)


def _san_cfg():
    return ModelConfig(name="san-tiny", family="dense", num_layers=2,
                       d_model=32, num_heads=2, num_kv_heads=1, head_dim=16,
                       d_ff=64, vocab_size=VOCAB, tie_embeddings=True)


def test_sanitizer_reports_leak_with_alloc_site():
    from repro.analysis import PageSanitizer

    san = PageSanitizer(8)
    lease = san.lease(fresh=2)
    san.annotate(lease, slot=3, rid=7)
    (line,) = san.leak_report()
    assert "leaked lease of 2 page(s)" in line
    assert "slot=3" in line and "rid=7" in line
    assert "test_analysis.py" in line  # the grant site, not the report site


def test_sanitizer_double_release_names_both_sites():
    from repro.analysis import PageSanitizer, SanitizerError

    san = PageSanitizer(4)
    lease = san.lease(fresh=1)
    san.release(lease)
    with pytest.raises(SanitizerError, match="double release") as ei:
        san.release(lease)
    msg = str(ei.value)
    assert "first released at" in msg and "test_analysis.py" in msg
    assert san.leak_report() == []


def test_sanitizer_raw_release_of_leased_page_is_evict_while_shared():
    from repro.analysis import PageSanitizer, SanitizerError

    san = PageSanitizer(4)
    lease = san.lease(fresh=2)
    with pytest.raises(SanitizerError, match="evict-while-shared") as ei:
        san.release([lease.ids()[0]])
    assert "test_analysis.py" in str(ei.value)  # names the holder's grant
    # a pinned page releases its pin without touching the lease's hold
    san.retain(lease.ids()[0])
    san.release([lease.ids()[0]])
    assert san.refcount(lease.ids()[0]) == 1
    san.release(lease)
    assert san.num_free == 4


def test_sanitizer_shared_write_requires_cow():
    from repro.analysis import PageSanitizer, SanitizerError

    san = PageSanitizer(4)
    owner = san.lease(fresh=1)
    sharer = san.lease(shared=owner.ids())
    with pytest.raises(SanitizerError, match="without a cow"):
        san.note_write(sharer.ids(), sharer)
    san.cow(sharer, 0)
    san.note_write(sharer.ids(), sharer)  # owned after the fault: fine
    san.release(owner)
    san.release(sharer)
    assert san.leak_report() == []


def _san_engine_run(cfg, params, sanitize, **kw):
    eng = ContinuousBatchingEngine(cfg, params, max_slots=4, max_seq=64,
                                   paged=True, page_size=8, num_pages=24,
                                   sanitize=sanitize, **kw)
    key = jax.random.PRNGKey(60)
    base = _prompt(key, 20)
    shared_tail = jnp.concatenate(
        [base[:, :17], _prompt(jax.random.fold_in(key, 1), 6)], axis=1)
    rids = [eng.submit(base, 6),            # registers its pages
            eng.submit(shared_tail, 5),     # radix hit + CoW partial page
            eng.submit(_prompt(jax.random.fold_in(key, 2), 11), 7),
            eng.submit(base[:, :9], 1)]     # answered at prefill, no slot
    done = {c.rid: c.tokens for c in eng.drain()}
    return [done[r] for r in rids], eng


def test_sanitized_engine_byte_identical_and_clean():
    """Clean shared-prefix/CoW/mixed-length runs finish with a zero-finding
    sanitizer report and tokens byte-identical to sanitize=False."""
    cfg = _san_cfg()
    params = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    base_toks, base_eng = _san_engine_run(cfg, params, sanitize=False)
    san_toks, san_eng = _san_engine_run(cfg, params, sanitize=True)
    assert san_eng.stats["shared_admits"] >= 1
    assert san_eng.stats["cow_copies"] >= 1
    assert san_eng.sanitizer_report() == []
    for a, b in zip(base_toks, san_toks):
        assert np.array_equal(a, b)
    assert base_eng.stats["decode_steps"] == san_eng.stats["decode_steps"]


def test_sanitized_engine_fixture_runs_clean(sanitized_engine):
    cfg = _san_cfg()
    params = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    eng = sanitized_engine(cfg, params, max_slots=2, max_seq=32,
                           page_size=8, num_pages=8)
    eng.submit(_prompt(jax.random.PRNGKey(61), 7), 4)
    assert len(eng.drain()) == 1
    assert eng.sanitizer_report() == []


def test_sanitized_engine_catches_injected_leak():
    """An eviction that drops the lease without releasing it surfaces at
    drain() as a leak naming the admitting call site."""
    from repro.analysis import SanitizerError

    cfg = _san_cfg()
    params = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2, max_seq=32,
                                   paged=True, page_size=8, num_pages=8,
                                   sanitize=True)
    orig_evict = eng._evict

    def leaky_evict(slot):
        eng._leases.pop(slot, None)  # injected: lease dropped, never released
        orig_evict(slot)

    eng._evict = leaky_evict
    eng.submit(_prompt(jax.random.PRNGKey(62), 7), 3)
    with pytest.raises(SanitizerError, match="leaked lease") as ei:
        eng.drain()
    assert "engine.py" in str(ei.value)  # grant site: _admit's lease() call


def test_sanitized_engine_catches_premature_release():
    """Releasing a live slot's lease out from under the engine trips the
    very next step's cross-check (and the engine's own eviction would be the
    double release)."""
    from repro.analysis import SanitizerError

    cfg = _san_cfg()
    params = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2, max_seq=32,
                                   paged=True, page_size=8, num_pages=8,
                                   sanitize=True)
    eng.submit(_prompt(jax.random.PRNGKey(63), 7), 4)
    eng.step()
    slot = int(np.nonzero(eng._active)[0][0])
    eng._allocator.release(eng._leases[slot])  # injected premature release
    with pytest.raises(SanitizerError):
        eng.drain()


def test_sanitized_engine_catches_evict_while_shared():
    """A raw page-id release of a page a live lease still maps (the bug class
    refcounting exists to prevent) is refused with the holder named."""
    from repro.analysis import SanitizerError

    cfg = _san_cfg()
    params = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2, max_seq=32,
                                   paged=True, page_size=8, num_pages=8,
                                   sanitize=True, prefix_cache=False)
    eng.submit(_prompt(jax.random.PRNGKey(64), 7), 4)
    eng.step()
    slot = int(np.nonzero(eng._active)[0][0])
    page = int(eng._leases[slot].page_ids[0])
    with pytest.raises(SanitizerError, match="evict-while-shared") as ei:
        eng._allocator.release([page])
    msg = str(ei.value)
    assert f"slot={slot}" in msg and "engine.py" in msg


def test_sanitized_engine_catches_missing_cow(monkeypatch):
    """If the CoW fault is skipped (the shared partial page handed to the
    sharer as-is), the suffix prefill's write into it is caught before it
    lands, naming the page's other holder."""
    from repro.analysis import SanitizerError

    cfg = _san_cfg()
    params = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    eng = ContinuousBatchingEngine(cfg, params, max_slots=4, max_seq=64,
                                   paged=True, page_size=8, num_pages=24,
                                   sanitize=True)
    key = jax.random.PRNGKey(65)
    base = _prompt(key, 12)
    eng.submit(base, 4)
    eng.drain()  # registers base's pages (incl. the partial second page)

    def broken_cow(lease, index):  # injected: no copy, share stays shared
        src = int(lease.page_ids[index])
        return src, src

    monkeypatch.setattr(eng._allocator, "cow", broken_cow)
    tail = jnp.concatenate([base[:, :10],
                            _prompt(jax.random.fold_in(key, 1), 5)], axis=1)
    eng.submit(tail, 4)  # radix hit with a partial-page extension
    with pytest.raises(SanitizerError, match="without a cow") as ei:
        eng.drain()
    assert "cow page copy" in str(ei.value)


def test_pool_exhaustion_reports_holders():
    """Satellite: the allocator's exhaustion error names who holds the pool —
    slots, index pins, and (sanitized) the grant sites."""
    cfg = _san_cfg()
    params = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2, max_seq=32,
                                   paged=True, page_size=8, num_pages=4,
                                   sanitize=True)
    eng.submit(_prompt(jax.random.PRNGKey(66), 9), 4)
    eng.step()
    with pytest.raises(RuntimeError, match="exhausted") as ei:
        eng._allocator.alloc(10)
    msg = str(ei.value)
    assert "current holders" in msg
    assert "slot 0" in msg and "grant sites" in msg
