"""Chip smoke run: the FedRefine serving path once, on one TPU chip.

Builds the paper's case-study pairing at published widths — receiver
Qwen3-0.6B, one Qwen2.5-0.5B transmitter and the fuser between them, random
bf16 weights from ``--seed`` — and serves a handful of standalone, C2C and T2T
requests through ``FedRefineSystem`` and the paged, chunked, prefix-cached
continuous-batching engine. It then checks that every request completed, that
the engine's mechanisms all ran (chunked prefill, a radix hit with a
copy-on-write page, one decode trace), that the decode step runs the compiled
Pallas kernels, and that what the engine served agrees with a float32
``jax.numpy`` reference: every request's tokens, step by step, and the
receiver's logits at the last prompt position and the first decode step.

    python chip_smoke.py [--seed 0]

It needs a TPU: anywhere else it exits non-zero before building anything.
The times it prints are smoke diagnostics, not benchmark metrics. Its last
line of standard output, printed only when every check passed, is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

The phases are plain functions of the model configurations and ``Sizes``, so
``tests/test_chip_smoke.py`` runs the same path at ``tiny_zoo`` widths on the
CPU, with the kernels in interpret mode.
"""
from __future__ import annotations

import argparse
import collections
import functools
import json
import math
import os
import sys
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import hw  # noqa: E402
from repro.configs.base import ModelConfig  # noqa: E402
from repro.configs.case_study import QWEN2_5_0_5B, QWEN3_0_6B  # noqa: E402
from repro.core.fedrefine import FedRefineSystem, Participant  # noqa: E402
from repro.core.protocol import PROTOCOLS  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.runtime import init_compile_cache  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.models.cache import FusedPrefix, SlotTable  # noqa: E402

#: Largest relative L2 error ||served - reference|| / ||reference|| allowed
#: on a logits vector. The served path keeps weights, activations, the KV
#: pool and the logits in bf16 (8 significant bits, unit roundoff 2^-8
#: = 0.4%); the reference runs the same bf16 weights in float32 at the
#: highest matmul precision. The residual stream alone is rounded twice per
#: layer, so 28 layers of rounding add up, in quadrature, to one or two
#: percent of the signal. 5% leaves room for that, while a wrong page, mask
#: or position moves the logits by tens of percent, and weights rounded to
#: fp8 (unit roundoff 2^-4) miss it too: ``check_reference`` runs that
#: control every time.
REL_TOL = 0.05

#: Largest amount, in units of the RMS of the reference's logits, by which
#: the engine's token at any step may score below the reference's best
#: token. Were the engine's logits within d (max abs) of the reference, its
#: pick would trail the best by at most 2d. Within REL_TOL the per-logit
#: error has an RMS of at most 0.05 RMS(ref), and the largest of 151,936
#: roughly Gaussian errors lies within 5 times that: 2d <= 0.5 RMS. Near
#: ties closer than that are common in bf16 (the top two of 151,936 draws
#: lie about 0.2 RMS apart). A token the reference does not rate trails
#: the best by about 4.5 RMS (the largest of 151,936 draws; about 3 RMS
#: over a 512-token vocabulary), so a wrong page or position shows.
TIE_TOL = 0.5


@dataclass(frozen=True)
class Sizes:
    """Engine geometry and request shapes of one smoke run."""

    max_slots: int
    max_seq: int
    page_size: int
    prefill_budget: int  # prompt tokens per engine step (chunked prefill)
    max_prefix: int  # fused C2C prefix bucket
    long_prompt: int  # prefills in several chunks
    shared_prefix: int  # tokens a second prompt shares with the long one
    tail: int  # the second prompt's own tokens after the shared prefix
    c2c_prompt: int  # <= max_prefix: the transmitter sees the same prompt
    short_prompt: int
    new_tokens: int


#: The chip run: 8 slots of 2,048 tokens over 16-token pages, 256 prompt
#: tokens per step. The long prompt takes four chunks; 600 shared tokens are
#: 37 full pages plus half a page, so the second prompt's radix hit shares
#: the full pages and copies the half one.
CHIP = Sizes(max_slots=8, max_seq=2048, page_size=16, prefill_budget=256,
             max_prefix=256, long_prompt=1000, shared_prefix=600, tail=64,
             c2c_prompt=200, short_prompt=64, new_tokens=32)


@dataclass
class Request:
    name: str
    protocol: str
    prompt: np.ndarray  # (S,) int32
    steps: int


# ------------------------------------------------------------------ build


def build(rx_cfg: ModelConfig, tx_cfg: ModelConfig,
          seed: int) -> FedRefineSystem:
    """Receiver, transmitter (bf16) and the fusers and gate between them,
    all from ``seed``."""
    key = jax.random.PRNGKey(seed)
    # one program per model: run eagerly, every weight shape compiles its
    # own random-normal program (two minutes on a v5e at published widths)
    init = jax.jit(T.init_params, static_argnums=(0, 2))
    members = [Participant(c.name, c, init(c, jax.random.fold_in(key, i),
                                           jnp.bfloat16))
               for i, c in enumerate((rx_cfg, tx_cfg))]
    return FedRefineSystem.build(members, seed=seed)


def make_requests(vocab: int, sizes: Sizes, seed: int) -> List[Request]:
    """Standalone, C2C and T2T requests of several lengths; ``shared`` repeats
    the first ``shared_prefix`` tokens of ``long``."""
    rng = np.random.default_rng(seed)

    def draw(n):
        return rng.integers(0, vocab, n, dtype=np.int32)

    long = draw(sizes.long_prompt)
    tail = draw(sizes.tail)
    # diverge right after the shared prefix: the radix match is exactly it
    tail[0] = (long[sizes.shared_prefix] + 1) % vocab
    shared = np.concatenate([long[:sizes.shared_prefix], tail])
    n = sizes.new_tokens
    return [Request("long", "standalone", long, n),
            Request("c2c", "c2c", draw(sizes.c2c_prompt), n),
            Request("t2t", "t2t", draw(sizes.short_prompt), n),
            Request("short", "standalone", draw(sizes.short_prompt), n),
            Request("shared", "standalone", shared, n)]


# ------------------------------------------------------------------ serve


def serve(system: FedRefineSystem, receiver: str, requests: List[Request],
          sizes: Sizes):
    """Submit every request to the receiver's paged, chunked engine and drain
    it. Returns ({name: completion}, engine)."""
    eng = system.make_engine(
        receiver, max_slots=sizes.max_slots, max_seq=sizes.max_seq,
        max_prefix=sizes.max_prefix, cache_dtype=jnp.bfloat16, paged=True,
        page_size=sizes.page_size, prefill_token_budget=sizes.prefill_budget)
    rids = {system.submit(receiver, r.prompt, r.steps, protocol=r.protocol):
            r.name for r in requests}
    done = system.drain(receiver)
    return {rids[rid]: out for rid, out in done.items()}, eng


def check_served(requests: List[Request], done: Dict[str, dict],
                 stats: dict) -> None:
    """Every request completed in full, and every mechanism of the path ran."""
    for r in requests:
        if r.name not in done:
            raise AssertionError(f"request {r.name!r} never completed")
        got = done[r.name]
        if len(got["tokens"]) != r.steps or got["protocol"] != r.protocol:
            raise AssertionError(
                f"request {r.name!r}: {len(got['tokens'])} {got['protocol']} "
                f"token(s), expected {r.steps} {r.protocol}")
    want = {"decode_traces": lambda v: v == 1,
            "prefill_traces": lambda v: v == 1,
            "prefill_chunks": lambda v: v > len(requests),
            "radix_hits": lambda v: v >= 1,
            "cow_copies": lambda v: v >= 1,
            "completed": lambda v: v == len(requests)}
    bad = {k: stats[k] for k, ok in want.items() if not ok(stats[k])}
    if bad:
        raise AssertionError(f"engine stats off: {bad} (all: {stats})")


# -------------------------------------------------------------- reference


def prepare(system: FedRefineSystem, receiver: str, r: Request):
    """The request as ``FedRefineSystem.submit`` hands it to the engine (the
    same transmitter schedule and default key): its receiver-side prompt
    (T2T prepends the transmitter's tokens) and fused prefix (C2C)."""
    proto = PROTOCOLS[r.protocol]
    tx = (system.schedule("default", receiver, 1)
          if proto.needs_transmitters() else [])
    return proto.prepare(system, receiver, jnp.asarray(r.prompt)[None], tx,
                         steps=r.steps, key=jax.random.PRNGKey(0))


def served_logits(eng, params: dict, prompt: np.ndarray,
                  fused: Optional[FusedPrefix], first: int):
    """One request through the engine's step functions, in one slot of the
    engine's geometry: token-budget chunks of ``transformer.prefill_chunk``
    into a page pool of the cache dtype (the ragged prefill kernel), then one
    paged ``decode_step`` (the paged decode kernel) on ``first``. Returns
    (logits at the last prompt position, logits at the first decode step)."""
    cfg, S, C = eng.cfg, len(prompt), eng.prefill_budget
    table = SlotTable.init(cfg, 1, eng.max_seq, eng.cache_dtype,
                           page_size=eng.page_size)
    pps = table.pages_per_slot
    n_pages = math.ceil((S + 1) / eng.page_size)
    page_row = jnp.where(jnp.arange(pps) < n_pages, jnp.arange(pps),
                         table.invalid_page).astype(jnp.int32)
    pre = eng.held_prefix(fused)
    # decode reads the prefix from a row table, as the engine's decode does
    row = FusedPrefix.empty(cfg, 1, eng.max_prefix,
                            eng.cache_dtype).insert_slot(0, pre)

    @jax.jit
    def served_chunk(params, table, toks, pos_offset, n_live, pre):
        logits, table = T.prefill_chunk(
            cfg, params, table, toks, pos_offset, n_live, page_row,
            block_q=C, extra_kv=pre.to_extra_kv(cfg))
        return logits[0], table

    @jax.jit
    def served_decode(params, table, tok, row):
        logits, _ = T.decode_step(cfg, params, table, tok,
                                  extra_kv=row.to_extra_kv(cfg))
        return logits[0]

    for off in range(0, S, C):
        n = min(C, S - off)
        toks = np.zeros((1, C), np.int32)
        toks[0, :n] = prompt[off:off + n]
        logits, table = served_chunk(params, table, jnp.asarray(toks),
                                     jnp.int32(off), jnp.int32(n), pre)
    table = table.adopt_slot(0, page_row, S)
    return logits[n - 1], served_decode(params, table,
                                        jnp.asarray([first], jnp.int32), row)


@functools.partial(jax.jit, static_argnums=(0, 5))
def _reference(cfg, params, seq, start, fused, n):
    ek = None if fused is None else fused.to_extra_kv(cfg)
    logits, _ = T.forward(cfg, params, seq, extra_kv=ek)
    return jax.lax.dynamic_slice_in_dim(logits[0], start, n)


def reference_logits(cfg: ModelConfig, params32: dict, prompt, fused,
                     tokens, width: int):
    """Teacher-forced float32 ``jax.numpy`` forward over ``prompt`` followed
    by ``tokens[:-1]``: no Pallas, no pages, no cache, the highest matmul
    precision, float32 weights and prefix. Returns the logits that predict
    each of ``tokens`` (n, V); the first row is the last prompt position's,
    the second the first decode step's. The sequence is right-padded to
    ``width`` (causal: the rows read stay exact) so one program serves
    every request."""
    prompt, tokens = np.asarray(prompt), np.asarray(tokens)
    seq = np.zeros((1, width), np.int32)
    seq[0, :len(prompt)] = prompt
    seq[0, len(prompt):len(prompt) + len(tokens) - 1] = tokens[:-1]
    f32 = (None if fused is None else jax.tree.map(
        lambda a: a.astype(jnp.float32), FusedPrefix.ensure(fused)))
    with jax.default_matmul_precision("highest"):
        return _reference(cfg, params32, jnp.asarray(seq),
                          jnp.int32(len(prompt) - 1), f32, len(tokens))


def _map_floats(fn, params: dict) -> dict:
    return jax.tree.map(
        lambda a: fn(a) if jnp.issubdtype(a.dtype, jnp.floating) else a,
        params)


def _round_to_fp8_precision(a: jax.Array) -> jax.Array:
    """``a`` rounded to nearest at 3 mantissa bits, fp8 e4m3's precision,
    in its own dtype. On the bits: XLA's TPU compiler drops a round trip
    through a narrower float type as a no-op."""
    drop = jnp.finfo(a.dtype).nmant - 3
    u = {2: jnp.uint16, 4: jnp.uint32}[a.dtype.itemsize]
    keep = ~((1 << drop) - 1) & ((1 << 8 * a.dtype.itemsize) - 1)
    bits = jax.lax.bitcast_convert_type(a, u)
    bits = (bits + u(1 << (drop - 1))) & u(keep)
    return jax.lax.bitcast_convert_type(bits, a.dtype)


def _rel_err(a, ref) -> float:
    a = np.asarray(a, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(a - ref) / np.linalg.norm(ref))


def check_reference(system: FedRefineSystem, eng, receiver: str,
                    requests: List[Request],
                    done: Dict[str, dict]) -> Dict[str, dict]:
    """Holds what the engine served to a float32 reference:

    - every request's token stream, step by step: each token the engine
      chose scores within ``TIE_TOL`` of the best token of the
      teacher-forced reference;
    - for the long standalone request and the C2C one, the logits at the
      last prompt position and at the first decode step, through the
      engine's step functions in one slot, within ``REL_TOL``;
    - a control: the same logits with the weights rounded to fp8 miss
      ``REL_TOL``, so the tolerance can tell a lower precision apart.

    Returns the readings."""
    rxp = system.participants[receiver]
    cfg = rxp.cfg
    params32 = jax.jit(lambda p: _map_floats(
        lambda a: a.astype(jnp.float32), p))(rxp.params)
    preps = {r.name: prepare(system, receiver, r) for r in requests}
    width = max(p.prompt.shape[1] + r.steps - 1
                for r, p in zip(requests, preps.values()))
    report: Dict[str, dict] = {}
    refs = {}
    for r in requests:
        prep, toks = preps[r.name], np.asarray(done[r.name]["tokens"])
        ref = np.asarray(reference_logits(cfg, params32, prep.prompt[0],
                                          prep.fused, toks, width),
                         np.float64)
        refs[r.name] = ref
        rms = np.sqrt(np.mean(ref * ref, axis=-1))
        gaps = (ref.max(-1) - ref[np.arange(len(toks)), toks]) / rms
        report[r.name] = {"worst_gap_rms": float(gaps.max()),
                          "reference_argmax_steps":
                          int(np.sum(ref.argmax(-1) == toks)),
                          "steps": len(toks)}
        if gaps.max() > TIE_TOL:
            step = int(gaps.argmax())
            raise AssertionError(
                f"{r.name}: the engine's token {int(toks[step])} at step "
                f"{step} trails the reference's best by {gaps[step]:.3f} "
                f"RMS (tolerance {TIE_TOL})")
    for name in ("long", "c2c"):
        prep, ref = preps[name], refs[name]
        first = int(done[name]["tokens"][0])
        last, step = served_logits(eng, rxp.params, prep.prompt[0],
                                   prep.fused, first)
        errs = {"last_prompt": _rel_err(last, ref[0]),
                "first_decode": _rel_err(step, ref[1])}
        report[name].update(errs)
        if max(errs.values()) > REL_TOL:
            raise AssertionError(f"{name}: logits off the float32 reference "
                                 f"by {errs} (tolerance {REL_TOL})")
        if prep.fused is not None:
            # how far the fused prefix moves the logits: the check can see it
            bare = reference_logits(cfg, params32, prep.prompt[0], None,
                                    done[name]["tokens"], width)
            report[name]["without_prefix"] = _rel_err(last, bare[0])
    coarse = jax.jit(lambda p: _map_floats(_round_to_fp8_precision, p))
    last8, _ = served_logits(eng, coarse(rxp.params), preps["long"].prompt[0],
                             None, int(done["long"]["tokens"][0]))
    control = _rel_err(last8, refs["long"][0])
    report["control_fp8_weights"] = {"last_prompt": control}
    if control <= REL_TOL:
        raise AssertionError(
            f"weights rounded to fp8 precision stay within the tolerance "
            f"({control} <= {REL_TOL}): it cannot tell a lower precision "
            f"apart")
    return report


# ------------------------------------------------------------------- main


def _device():
    """The chip this process holds; exits non-zero on anything but a TPU."""
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        sys.exit(2)
    return dev, len(devices)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev, count = _device()
    cache_dir = init_compile_cache()
    peaks = hw.peaks(dev.device_kind)  # unknown chip: KeyError
    if ops._interpret():
        raise AssertionError("Pallas kernels would run in interpret mode")
    compile_s: Dict[str, float] = collections.defaultdict(float)

    def on_event(event, duration, fun_name="?", **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            name = fun_name.removeprefix("jit(").removesuffix(")")
            compile_s[name] += duration

    jax.monitoring.register_event_duration_secs_listener(on_event)
    print(f"device: {dev.device_kind} x{count}; peaks {peaks.bf16_flops:.3g} "
          f"FLOP/s bf16, {peaks.hbm_bw:.3g} B/s HBM ({peaks.source})")
    print(f"compile cache: {cache_dir}")

    t0 = time.perf_counter()
    system = build(QWEN3_0_6B, QWEN2_5_0_5B, args.seed)
    rx = QWEN3_0_6B.name
    requests = make_requests(QWEN3_0_6B.vocab_size, CHIP, args.seed)
    print(f"build: {time.perf_counter() - t0:.1f} s")

    t0 = time.perf_counter()
    done, eng = serve(system, rx, requests, CHIP)
    wall = time.perf_counter() - t0
    check_served(requests, done, eng.stats)
    hlo = eng.lower_decode().as_text()
    if "tpu_custom_call" not in hlo:
        raise AssertionError("the decode step holds no compiled Pallas kernel")
    n_tok = sum(len(d["tokens"]) for d in done.values())
    print(f"serve: {wall:.1f} s wall (compiles included), {n_tok} tokens "
          f"over {len(done)} requests")
    print(f"engine stats: {json.dumps(eng.stats, sort_keys=True)}")

    t0 = time.perf_counter()
    report = check_reference(system, eng, rx, requests, done)
    print(f"reference check: {time.perf_counter() - t0:.1f} s; "
          f"{json.dumps(report, sort_keys=True)}")
    # decode and cprefill are the engine's two step programs; the others
    # are this script's own (model init, served path, reference)
    steps = ("init_params", "decode", "cprefill", "served_chunk",
             "served_decode", "_reference")
    print("compile seconds (a persistent-cache hit counts as a compile): "
          + ", ".join(f"{n}={compile_s[n]:.1f}" for n in steps)
          + f"; all programs {sum(compile_s.values()):.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))


if __name__ == "__main__":
    main()
