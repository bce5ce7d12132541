"""Federation protocols and opportunistic protocol selection.

Paper §Possible Variants: "the decision to use cache or token communication
could be dynamically determined based on both the current network status and
the specific QoS requirements".

Each way participants can collaborate is a :class:`FederationProtocol`
(Standalone / C2C / T2T) bundling the three things that were previously
scattered across ``choose_protocol`` + ``fedrefine.submit`` +
``fedrefine.serve_opportunistic``:

  * an analytic **latency estimate** per link (the QoS input),
  * a **quality rank** (paper Fig. 3a: c2c > t2t > standalone),
  * **prepare()** — the transmit/prefix construction that turns a raw request
    into what the receiver's engine decodes (a fused KV prefix for C2C, a
    combined shared-token prompt for T2T, the prompt itself standalone).

``FedRefineSystem`` and ``launch/engine.py`` consume protocols only through
this interface, so adding a protocol variant is additive (register it in
``PROTOCOLS``), not a cross-module edit.

Latency model per link:

  latency_c2c = kv_bytes(seq)/bw + rtt + fuser_time + decode_time
  latency_t2t = tx_gen_time + text_bytes/bw + rtt + rx_prefill_time + decode_time

Compute-time terms come from the TPU v5e row of the per-device peak table
(repro/hw.py — one shared source with roofline.py), so the protocol's
decisions stay consistent with the §Roofline tables. Properties pinned by tests: decisions are monotone
in bandwidth (more bandwidth never flips C2C→T2T) and respect QoS feasibility.
"""
from __future__ import annotations

import abc
from dataclasses import dataclass, field
from typing import (Any, Dict, FrozenSet, List, Literal, Optional, Tuple)

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import commload
from repro.hw import V5E  # the chip the latency model assumes
from repro.models.cache import FusedPrefix


@dataclass(frozen=True)
class LinkModel:
    bandwidth_bps: float  # bytes/s on the federation link
    rtt_s: float = 0.0

    def transfer_time(self, nbytes: float) -> float:
        return nbytes / self.bandwidth_bps + self.rtt_s


@dataclass(frozen=True)
class QoS:
    max_latency_s: float = float("inf")
    min_quality: Literal["standalone", "t2t", "c2c"] = "standalone"


def _prefill_time(cfg: ModelConfig, seq: int, mfu: float = 0.4) -> float:
    return 2.0 * cfg.active_param_count() * seq / (V5E.bf16_flops * mfu)


def _decode_time(cfg: ModelConfig, steps: int, hbm_frac: float = 0.6) -> float:
    # decode is memory-bound: one full weight read per token
    return steps * 2.0 * cfg.active_param_count() / (V5E.hbm_bw * hbm_frac)


def _fuser_time(cfg_tx: ModelConfig, cfg_rx: ModelConfig, seq: int,
                mfu: float = 0.4) -> float:
    d_in = 2 * cfg_tx.kv_dim
    d_out = 2 * cfg_rx.kv_dim
    d_h = max(d_in, d_out)
    n = len(cfg_rx.attention_layers)
    flops = 2.0 * seq * n * (d_in * d_h + d_h * d_h + d_h * d_out)
    return flops / (V5E.bf16_flops * mfu)


# ------------------------------------------------------------ prepared form


@dataclass
class PreparedRequest:
    """A protocol's output: exactly what the receiver engine decodes."""

    prompt: jax.Array  # receiver-side tokens (B, S) — combined for T2T
    protocol: str
    fused: Optional[FusedPrefix] = None  # C2C prefix (None otherwise)
    transmitters: List[str] = field(default_factory=list)
    wire_bytes: int = 0  # bytes this request put on the federation link
    meta: dict = field(default_factory=dict)


# ---------------------------------------------------------------- protocols


class FederationProtocol(abc.ABC):
    """One way participants collaborate on a request."""

    name: str = "?"
    quality: int = 0  # higher = better answer quality (paper Fig. 3a)

    @abc.abstractmethod
    def estimate_latency(self, cfg_txs: List[ModelConfig], cfg_rx: ModelConfig,
                         seq: int, gen_steps: int, link: LinkModel, *,
                         shared_tokens: int = 64) -> float:
        """End-to-end latency of one request under this protocol."""

    @abc.abstractmethod
    def prepare(self, system: Any, receiver: str, prompt: jax.Array,
                tx_names: List[str], *, steps: int, key: jax.Array,
                gated: bool = True,
                tx_prompts: Optional[Dict[str, jax.Array]] = None
                ) -> PreparedRequest:
        """Run the transmit side and build the receiver's decode inputs.
        ``system`` is a FedRefineSystem (duck-typed to avoid a cycle)."""

    def needs_transmitters(self) -> bool:
        return True


class Standalone(FederationProtocol):
    name = "standalone"
    quality = 0

    def estimate_latency(self, cfg_txs: List[ModelConfig],
                         cfg_rx: ModelConfig, seq: int, gen_steps: int,
                         link: LinkModel, *,
                         shared_tokens: int = 64) -> float:
        return _prefill_time(cfg_rx, seq) + _decode_time(cfg_rx, gen_steps)

    def prepare(self, system: Any, receiver: str, prompt: jax.Array,
                tx_names: List[str], *, steps: int, key: jax.Array,
                gated: bool = True,
                tx_prompts: Optional[Dict[str, jax.Array]] = None
                ) -> PreparedRequest:
        return PreparedRequest(prompt=prompt, protocol=self.name)

    def needs_transmitters(self) -> bool:
        return False


class C2C(FederationProtocol):
    """Cache-to-cache: transmitters prefill locally, ship their KV stacks
    through the system's wire channel, the fuser projects them into receiver
    space, the receiver decodes over [fused ∘ own] (Eq. 4)."""

    name = "c2c"
    quality = 2

    def estimate_latency(self, cfg_txs: List[ModelConfig],
                         cfg_rx: ModelConfig, seq: int, gen_steps: int,
                         link: LinkModel, *,
                         shared_tokens: int = 64) -> float:
        xfer = link.transfer_time(commload.c2c_bytes_total(cfg_txs, seq))
        fuse = sum(_fuser_time(t, cfg_rx, seq) for t in cfg_txs)
        return xfer + fuse + _decode_time(cfg_rx, gen_steps)

    def prepare(self, system: Any, receiver: str, prompt: jax.Array,
                tx_names: List[str], *, steps: int, key: jax.Array,
                gated: bool = True,
                tx_prompts: Optional[Dict[str, jax.Array]] = None
                ) -> PreparedRequest:
        if tx_prompts is None:
            tx_prompts = {
                n: system.rephrase(prompt, jax.random.fold_in(key, i))
                for i, n in enumerate(tx_names)
            }
        stacks, wire_bytes = system.transmit_stacks(tx_names, tx_prompts)
        fused = system.fused_prefix(receiver, tx_names, stacks, gated=gated)
        return PreparedRequest(prompt=prompt, protocol=self.name, fused=fused,
                               transmitters=list(tx_names),
                               wire_bytes=wire_bytes)


class T2T(FederationProtocol):
    """Text-to-text: transmitters answer as generated tokens; the receiver
    re-prefills [shared ∘ own prompt] — the prefill rebuild C2C avoids."""

    name = "t2t"
    quality = 1

    def estimate_latency(self, cfg_txs: List[ModelConfig],
                         cfg_rx: ModelConfig, seq: int, gen_steps: int,
                         link: LinkModel, *,
                         shared_tokens: int = 64) -> float:
        tx_gen = (max(_decode_time(t, shared_tokens) for t in cfg_txs)
                  if cfg_txs else 0.0)
        xfer = link.transfer_time(
            commload.t2t_bytes_total(len(cfg_txs), shared_tokens))
        rx_prefill = _prefill_time(cfg_rx, seq + shared_tokens * len(cfg_txs))
        return tx_gen + xfer + rx_prefill + _decode_time(cfg_rx, gen_steps)

    def prepare(self, system: Any, receiver: str, prompt: jax.Array,
                tx_names: List[str], *, steps: int, key: jax.Array,
                gated: bool = True,
                tx_prompts: Optional[Dict[str, jax.Array]] = None
                ) -> PreparedRequest:
        from repro.core import t2t

        shared: List[jax.Array] = []
        wire_bytes = 0
        for i, n in enumerate(tx_names):
            p = system.participants[n]
            tp = (tx_prompts[n] if tx_prompts is not None
                  else system.rephrase(prompt, jax.random.fold_in(key, i)))
            toks = t2t.t2t_exchange(p.cfg, p.params, tp, steps)
            shared.append(toks)
            wire_bytes += int(toks.size) * commload.t2t_bytes_per_token()
        combined = jnp.concatenate([*shared, prompt], axis=1)
        return PreparedRequest(prompt=combined, protocol=self.name,
                               transmitters=list(tx_names),
                               wire_bytes=wire_bytes)


#: Registry consumed by FedRefineSystem / ContinuousBatchingEngine. Adding a
#: protocol variant == adding an entry here.
PROTOCOLS: Dict[str, FederationProtocol] = {
    p.name: p for p in (C2C(), T2T(), Standalone())
}

#: Names in quality order, best first (paper Fig. 3a).
QUALITY_ORDER: List[str] = sorted(
    PROTOCOLS, key=lambda n: -PROTOCOLS[n].quality)


# ------------------------------------------------------------ wire contracts


#: Wire dtypes no schema may carry: int64/uint64 token ids double the wire
#: bytes for no information, float64 stacks quadruple them, and object
#: payloads are not tensors at all. The WireAuditor rejects these regardless
#: of the per-protocol schema.
FORBIDDEN_WIRE_DTYPES: FrozenSet[str] = frozenset(
    {"int64", "uint64", "float64"})


@dataclass(frozen=True)
class WireSchema:
    """Declared wire contract of one protocol: which media may cross the
    federation link, at which dtypes, through which codec stages.

    The static pass (repro.analysis.wire, WIR004) cross-checks declared
    ``stages`` against codec ``Pipeline`` literals; the runtime twin
    (repro.analysis.wire_audit.WireAuditor) verifies every encoded
    :class:`~repro.core.transport.Message` against the schema and its byte
    estimate. ``Message`` is duck-typed here so protocol.py keeps its
    layering (it never imports transport.py)."""

    protocol: str
    #: media allowed on the wire — subset of {"stack", "tokens"}
    media: FrozenSet[str] = frozenset()
    #: dtypes a *dense* stack may ship at; empty = any non-forbidden dtype
    stack_dtypes: FrozenSet[str] = frozenset()
    #: codec stages the wire pipeline must apply ("quant", "rephrase", ...)
    stages: Tuple[str, ...] = ()
    #: relative slack between measured bytes_on_wire and the estimate
    tolerance: float = 0.0
    #: hard per-message byte ceiling (None = only the QoS budget applies)
    max_message_bytes: Optional[int] = None

    def estimate_wire_bytes(self, msg: Any) -> int:
        """commload-analytic wire bytes of a *pre-encode* message under this
        schema's declared stages: an int8-quantised stack costs exactly
        ``quant.quantized_bytes``, a dense one ``commload.measured_bytes``,
        tokens ``t2t_bytes_per_token`` each."""
        total = 0
        stack = getattr(msg, "stack", None)
        if stack is not None:
            if "quant" in self.stages:
                from repro.core import quant

                total += quant.quantized_bytes(stack)
            else:
                total += commload.measured_bytes(stack)
        tokens = getattr(msg, "tokens", None)
        if tokens is not None:
            total += int(tokens.size) * commload.t2t_bytes_per_token()
        payload = getattr(msg, "payload", None)
        if payload:
            total += commload.measured_bytes(payload)
        return total


#: Per-protocol wire contracts, keyed like PROTOCOLS. The defaults describe
#: the in-tree wire (FedRefineSystem defaults to an IdentityChannel, so a
#: dense stack at a working dtype is legal for C2C); tests and deployments
#: pass stricter schemas (e.g. stages=("quant",) + stack_dtypes={"int8"}) to
#: the WireAuditor to *forbid* dense KV on the link.
WIRE_SCHEMAS: Dict[str, WireSchema] = {
    "c2c": WireSchema(
        protocol="c2c", media=frozenset({"stack"}),
        stack_dtypes=frozenset({"bfloat16", "float16", "float32", "int8"})),
    "t2t": WireSchema(protocol="t2t", media=frozenset({"tokens"})),
    "standalone": WireSchema(protocol="standalone"),
}


# --------------------------------------------------- legacy latency wrappers


def latency_c2c(cfg_txs: List[ModelConfig], cfg_rx: ModelConfig, seq: int,
                gen_steps: int, link: LinkModel) -> float:
    return PROTOCOLS["c2c"].estimate_latency(cfg_txs, cfg_rx, seq, gen_steps,
                                             link)


def latency_t2t(cfg_txs: List[ModelConfig], cfg_rx: ModelConfig, seq: int,
                gen_steps: int, link: LinkModel, shared_tokens: int) -> float:
    return PROTOCOLS["t2t"].estimate_latency(cfg_txs, cfg_rx, seq, gen_steps,
                                             link, shared_tokens=shared_tokens)


def latency_standalone(cfg_rx: ModelConfig, seq: int, gen_steps: int) -> float:
    return PROTOCOLS["standalone"].estimate_latency([], cfg_rx, seq, gen_steps,
                                                    LinkModel(1.0))


# ------------------------------------------------------------------ chooser


def choose_protocol(
    cfg_txs: List[ModelConfig],
    cfg_rx: ModelConfig,
    seq: int,
    gen_steps: int,
    link: LinkModel,
    qos: QoS,
    *,
    shared_tokens: int = 64,
) -> dict:
    """Pick the highest-quality protocol that satisfies the QoS latency budget.

    Quality order (paper Fig. 3a): c2c > t2t > standalone.
    """
    cands = {
        name: PROTOCOLS[name].estimate_latency(
            cfg_txs, cfg_rx, seq, gen_steps, link, shared_tokens=shared_tokens)
        for name in QUALITY_ORDER
    }
    floor = QUALITY_ORDER.index(qos.min_quality)
    # best quality, down to (and including) the QoS quality floor, that fits
    for name in QUALITY_ORDER[: floor + 1]:
        if cands[name] <= qos.max_latency_s:
            return {"protocol": name, "latencies": cands, "qos_met": True}
    # infeasible QoS: degrade to the fastest candidate and flag it
    fastest = min(cands, key=lambda n: cands[n])
    return {"protocol": fastest, "latencies": cands, "qos_met": False}
