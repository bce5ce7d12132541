"""FedRefine — the paper's federated-inference orchestrator (Fig. 2, Eq. 4).

A ``FedRefineSystem`` holds N heterogeneous participants, the server-side fuser
registry, per-receiver gating networks, and a task-affinity scheduler ("the
receiver model selects different model combinations according to the different
tasks", §Case Study). One refined inference:

  1. privacy: every participant receives its own rephrased prompt,
  2. transmitters prefill locally and export their KV stacks,
  3. the stacks cross the federation ``wire`` (core/transport.py channel:
     identity, int8, or a composed pipeline) — byte-accounted per request,
  4. the server (here: receiver-side) projects each stack through F_{j,i},
  5. gating weighs each fused cache,
  6. the receiver decodes per Eq. 4 over [fused_1 ∘ … ∘ fused_s ∘ own].

Protocol mechanics (how a request becomes engine inputs) live in
``core/protocol.PROTOCOLS`` — this orchestrator only schedules transmitters,
owns the participants/registry/wire, and drives the engines.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import c2c
from repro.core import transport as TR
from repro.core.privacy import ParaphraseChannel
from repro.core.protocol import PROTOCOLS
from repro.core.registry import FuserRegistry
from repro.models import transformer as T
from repro.models.cache import FusedPrefix, KVStack


@partial(jax.jit, static_argnums=0)
def transmit_program(cfg: ModelConfig, params: dict,
                     tokens: jax.Array) -> KVStack:
    """Step 2 on the transmitter, as one program: prefill ``tokens`` (B, S)
    and export the S rows of every attention layer. Built once per
    (config, prompt shape); the weights are arguments, never constants."""
    S = tokens.shape[1]
    _, cache = T.prefill(cfg, params, tokens, max_seq=S)
    return cache.export_stack(cfg, length=S)


@partial(jax.jit, static_argnames=("cfg_txs", "cfg_rx", "use_kernel"))
def fuse_program(fusers: List[dict], gating: Optional[dict],
                 stacks: List[KVStack], *, cfg_txs: Tuple[ModelConfig, ...],
                 cfg_rx: ModelConfig, use_kernel: bool = False) -> FusedPrefix:
    """Steps 4-5 on the receiver, as one program: ``c2c.fused_prefix``.
    ``gating=None`` runs ungated; its pytree structure keys the program."""
    return c2c.fused_prefix(fusers, list(cfg_txs), cfg_rx, stacks,
                            gating=gating, use_kernel=use_kernel)


@dataclass
class Participant:
    name: str
    cfg: ModelConfig
    params: dict


@dataclass
class FedRefineSystem:
    participants: Dict[str, Participant]
    registry: FuserRegistry
    channel: Optional[ParaphraseChannel] = None
    # on-the-wire codec for transmitted KV stacks (core/transport.py);
    # IdentityChannel ships raw bf16/fp32, QuantChannel ships int8+scales.
    wire: TR.Channel = field(default_factory=TR.IdentityChannel)
    # task -> preferred transmitter names, best first (the case-study prior)
    task_affinity: Dict[str, List[str]] = field(default_factory=dict)
    # receiver name -> continuous-batching engine (see make_engine/submit/drain)
    engines: Dict[str, object] = field(default_factory=dict)
    # transmitter program calls, and the (config, prompt shape) programs
    # they built; a warmed window adds calls only
    stats: Dict[str, int] = field(
        default_factory=lambda: {"tx_calls": 0, "tx_programs": 0})
    _tx_shapes: Set[tuple] = field(default_factory=set, init=False,
                                   repr=False)

    # ------------------------------------------------------------- setup
    @classmethod
    def build(cls, members: Sequence[Participant],
              channel: Optional[ParaphraseChannel] = None,
              wire: Optional[TR.Channel] = None, *,
              audit_wire: bool = False,
              wire_schemas: Optional[dict] = None,
              seed: int = 0) -> "FedRefineSystem":
        """``seed`` seeds the registry's fusers and gates (the same in every
        process). ``audit_wire=True`` wraps the wire in a
        :class:`~repro.analysis.wire_audit.WireAuditor`: every transmitted
        message is verified against the protocol's declared WireSchema
        (media, dtypes, codec stages, commload byte accounting) and
        violations raise naming the producing call site. ``wire_schemas``
        overrides the registry defaults (else they are derived from the
        wire's codec composition)."""
        reg = FuserRegistry({m.name: m.cfg for m in members}, seed=seed)
        reg.ensure_all_pairs()
        wire = wire or TR.IdentityChannel()
        if audit_wire:
            from repro.analysis.wire_audit import WireAuditor

            wire = WireAuditor(wire, schemas=wire_schemas)
        return cls({m.name: m for m in members}, reg, channel, wire)

    # ------------------------------------------------------------- scheduling
    def schedule(self, task: str, receiver: str, n_tx: int) -> List[str]:
        """Pick transmitters for ``task`` (affinity order, else registry order)."""
        prefs = self.task_affinity.get(task, [])
        cands = [n for n in prefs if n != receiver and n in self.participants]
        cands += [n for n in self.participants
                  if n != receiver and n not in cands
                  and (n, receiver) in self.registry.fusers]
        return [n for n in cands if (n, receiver) in self.registry.fusers][:n_tx]

    # ------------------------------------------------------------- inference
    def rephrase(self, tokens: jax.Array, key) -> jax.Array:
        if self.channel is None:
            return tokens
        return self.channel.rephrase(tokens, key)

    def transmit_stacks(self, tx_names: List[str],
                        prompts: Dict[str, jax.Array]
                        ) -> Tuple[List[KVStack], int]:
        """Steps 2–3: local prefill at each transmitter; export KV stacks and
        ship them through the wire channel. Returns (received stacks, total
        bytes the link carried)."""
        if hasattr(self.wire, "expect"):  # WireAuditor: declare the protocol
            self.wire.expect(protocol="c2c")
        stacks, wire_bytes = [], 0
        for n in tx_names:
            p = self.participants[n]
            tokens = prompts[n]
            with jax.profiler.TraceAnnotation("fr.tx_prefill", tx=n):
                stack = transmit_program(p.cfg, p.params, tokens)
            self._tx_shapes.add((p.cfg, tokens.shape))
            self.stats["tx_calls"] += 1
            self.stats["tx_programs"] = len(self._tx_shapes)
            with jax.profiler.TraceAnnotation("fr.tx_export"):
                msg = TR.stack_message(stack)
            with jax.profiler.TraceAnnotation("fr.wire"):
                received, nbytes = self.wire.transmit(msg)
            stacks.append(received.stack)
            wire_bytes += nbytes
        return stacks, wire_bytes

    def fused_prefix(self, receiver: str, tx_names: List[str],
                     stacks: List[KVStack], *, gated: bool = True,
                     use_kernel: bool = False) -> FusedPrefix:
        with jax.profiler.TraceAnnotation("fr.fuse"):
            rxp = self.participants[receiver]
            fusers = [self.registry.get(n, receiver) for n in tx_names]
            cfg_txs = tuple(self.participants[n].cfg for n in tx_names)
            gating = self.registry.ensure_gating(receiver) if gated else None
            return fuse_program(fusers, gating, stacks,
                                cfg_txs=cfg_txs, cfg_rx=rxp.cfg,
                                use_kernel=use_kernel)

    def refine_generate(
        self,
        receiver: str,
        prompt: jax.Array,  # receiver-side (already rephrased) prompt (B, S)
        steps: int,
        *,
        task: str = "default",
        n_tx: int = 1,
        tx_prompts: Optional[Dict[str, jax.Array]] = None,
        key: Optional[jax.Array] = None,
        gated: bool = True,
    ) -> dict:
        """Full FedRefine inference (Eq. 4). Returns tokens + diagnostics."""
        key = key if key is not None else jax.random.PRNGKey(0)
        tx_names = self.schedule(task, receiver, n_tx)
        rxp = self.participants[receiver]
        proto = PROTOCOLS["c2c" if tx_names else "standalone"]
        prep = proto.prepare(self, receiver, prompt, tx_names, steps=steps,
                             key=key, gated=gated, tx_prompts=tx_prompts)
        toks = c2c.generate(rxp.cfg, rxp.params, prep.prompt, steps,
                            fused=prep.fused)
        return {
            "tokens": toks,
            "transmitters": tx_names,
            "c2c_bytes": prep.wire_bytes,
        }

    # ------------------------------------------------- continuous serving
    def make_engine(self, receiver: str, *, max_slots: int = 8,
                    max_seq: int = 128, max_prefix: int = 32,
                    cache_dtype=None, prompt_bucket: Optional[int] = None,
                    **engine_kw):
        """Build (and register) the receiver's continuous-batching engine.

        All protocols share it: standalone and T2T requests decode alongside
        C2C-fused ones in the same slot table (launch/engine.py). Extra
        keywords (``paged=True``, ``page_size=``, ``num_pages=``,
        ``admit_batch=``, ``sanitize=True`` for the page-lifecycle
        sanitizer) reach the engine unchanged."""
        from repro.launch.engine import ContinuousBatchingEngine

        rxp = self.participants[receiver]
        eng = ContinuousBatchingEngine(
            rxp.cfg, rxp.params, max_slots=max_slots, max_seq=max_seq,
            max_prefix=max_prefix,
            cache_dtype=cache_dtype if cache_dtype is not None else jnp.float32,
            prompt_bucket=prompt_bucket, **engine_kw)
        self.engines[receiver] = eng
        return eng

    def submit(self, receiver: str, prompt: jax.Array, steps: int, *,
               protocol: str = "c2c", task: str = "default", n_tx: int = 1,
               tx_prompts: Optional[Dict[str, jax.Array]] = None,
               key: Optional[jax.Array] = None, gated: bool = True) -> int:
        """Queue one request (B=1) into the receiver's engine; returns its rid.

        ``prompt`` is the receiver-side (already rephrased) prompt, as in
        refine_generate; pass ``tx_prompts`` to give each transmitter its own
        rephrasing of the *original* prompt (otherwise the receiver prompt is
        re-rephrased, compounding paraphrase noise on non-idempotent channels).

        ``protocol`` names an entry of core/protocol.PROTOCOLS ("c2c", "t2t",
        "standalone"). An explicit protocol that needs transmitters but has no
        schedulable one raises rather than silently degrading to standalone.
        Requests of all kinds coexist in one decode batch; drain() (or
        engine.step()) runs them to completion."""
        with jax.profiler.TraceAnnotation("fr.submit", protocol=protocol):
            if protocol not in PROTOCOLS:
                raise ValueError(f"unknown protocol {protocol!r}; "
                                 f"have {sorted(PROTOCOLS)}")
            proto = PROTOCOLS[protocol]
            eng = self.engines.get(receiver) or self.make_engine(receiver)
            key = key if key is not None else jax.random.PRNGKey(0)
            prompt = jnp.asarray(prompt, jnp.int32)
            if prompt.ndim == 1:
                prompt = prompt[None]
            tx_names = (self.schedule(task, receiver, n_tx)
                        if proto.needs_transmitters() else [])
            if proto.needs_transmitters() and not tx_names:
                raise ValueError(
                    f"protocol {protocol!r} requested but no transmitter "
                    f"with a fuser for receiver {receiver!r} is schedulable; "
                    f"submit with protocol='standalone' to run unrefined")
            with jax.profiler.TraceAnnotation("fr.prepare"):
                prep = proto.prepare(self, receiver, prompt, tx_names,
                                     steps=steps, key=key, gated=gated,
                                     tx_prompts=tx_prompts)
            return eng.submit(prep.prompt, steps, fused=prep.fused,
                              protocol=proto.name,
                              meta={"transmitters": tx_names,
                                    "wire_bytes": prep.wire_bytes}
                              if tx_names else {})

    def drain(self, receiver: str) -> Dict[int, dict]:
        """Run the receiver's engine until idle; {rid: completion dict}."""
        eng = self.engines[receiver]
        return {
            c.rid: {"tokens": c.tokens, "protocol": c.protocol, **c.meta}
            for c in eng.drain()
        }

    # ---------------------------------------------------- opportunistic serve
    def serve_opportunistic(
        self,
        receiver: str,
        prompt: jax.Array,
        steps: int,
        *,
        link,  # core.protocol.LinkModel
        qos,  # core.protocol.QoS
        task: str = "default",
        n_tx: int = 1,
        key: Optional[jax.Array] = None,
    ) -> dict:
        """Paper §Possible Variants: pick C2C vs T2T vs standalone per the
        current link + QoS, then execute that protocol end to end."""
        from repro.core import protocol as P

        key = key if key is not None else jax.random.PRNGKey(0)
        tx_names = self.schedule(task, receiver, n_tx)
        rxp = self.participants[receiver]
        cfg_txs = [self.participants[n].cfg for n in tx_names]
        decision = P.choose_protocol(
            cfg_txs, rxp.cfg, seq=int(prompt.shape[1]), gen_steps=steps,
            link=link, qos=qos)
        proto = PROTOCOLS[decision["protocol"] if tx_names else "standalone"]
        if hasattr(self.wire, "set_budget"):  # WireAuditor: QoS byte ceiling
            budget = link.bandwidth_bps * qos.max_latency_s
            self.wire.set_budget(
                int(budget) if math.isfinite(budget) else None)
        prep = proto.prepare(self, receiver, prompt, tx_names, steps=steps,
                             key=key)
        toks = c2c.generate(rxp.cfg, rxp.params, prep.prompt, steps,
                            fused=prep.fused)
        return {"tokens": toks, "protocol": proto.name, "decision": decision,
                "transmitters": prep.transmitters,
                "wire_bytes": prep.wire_bytes}
