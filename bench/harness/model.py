"""The system under test, built from a configuration file and a seed.

The configuration file holds each model's sizes under the keys of its public
``config.json``. The weights are the benchmark's: made on the device in one
jitted call from the seed, in the dtype they are served in, laid out as the
program's own ``init_params`` / ``init_fuser`` / ``init_gating`` lay them out
(their shapes are read with ``jax.eval_shape``, never run). The plain
reference (``reference.py``) reads the same arrays, so neither side takes
anything the other made.
"""
from __future__ import annotations

import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import fuser as F
from repro.core.fedrefine import FedRefineSystem, Participant
from repro.core.gating import init_gating
from repro.core.registry import FuserRegistry
from repro.models import transformer as T

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
SIDES = ("receiver", "transmitter")


def configs(config: Dict, archs) -> Tuple[ModelConfig, ModelConfig]:
    """The program's configs of the receiver and the transmitter, each
    mapped by the ``program_config`` of its architecture (``archs``, the
    program sides found by ``Spec.architecture``, in the order of
    ``SIDES``)."""
    rx, tx = (a.program_config(config[s]["name"], config[s])
              for a, s in zip(archs, SIDES))
    return rx, tx


def alignment(n_rx: int, n_tx: int) -> np.ndarray:
    """Bottom-up layer alignment (the paper's case study): receiver layer r
    reads transmitter layer min(r, n_tx - 1)."""
    return np.minimum(np.arange(n_rx), n_tx - 1).astype(np.int32)


def _leaf_value(path: str, shape, dtype, key, rules: Dict):
    """The benchmark's random value for one weight, by its name in the
    program's layout: weights N(0, 1/fan_in), so every block adds O(1) to
    the residual stream; the (tied) embedding N(0, 0.02^2), the published
    ``initializer_range``, so the prediction rests on the context and not on
    the input token alone; biases N(0, 0.1^2), norm scales 1 + N(0, 0.1^2),
    fuser gates -1 + N(0, 0.5^2). A leaf none of these name takes the rule
    its architecture adds under its name (``rules``: name -> f(z, shape),
    with z the same N(0, 1) draw)."""
    leaf = path.rsplit("/", 1)[-1]
    z = jax.random.normal(key, shape, jnp.float32)
    if leaf == "table":
        x = 0.02 * z
    elif leaf == "w":
        x = z * shape[-2] ** -0.5
    elif leaf == "b":
        x = 0.1 * z
    elif leaf in ("scale", "q_norm", "k_norm"):
        x = 1.0 + 0.1 * z
    elif leaf == "gate":
        x = -1.0 + 0.5 * z
    elif leaf in rules:
        x = rules[leaf](z, shape)
    else:
        raise KeyError(f"no rule for weight {path!r}")
    return x.astype(dtype)


def _path_name(path) -> str:
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return "/".join(parts)


def make_weights(rx: ModelConfig, tx: ModelConfig, seed: int,
                 dtype=jnp.bfloat16, *, rules) -> Dict:
    """Receiver, transmitter, fuser (transmitter -> receiver) and gate, from
    ``seed``, in one jitted call on the default device. Model weights are
    ``dtype``; norms, fuser and gate keep the program's float32. ``rules``:
    the ``WEIGHT_RULES`` of the receiver's and the transmitter's
    architectures."""
    k0 = jax.random.PRNGKey(0)
    shapes = {
        "rx": jax.eval_shape(lambda k: T.init_params(rx, k, dtype), k0),
        "tx": jax.eval_shape(lambda k: T.init_params(tx, k, dtype), k0),
        "fuser": jax.eval_shape(lambda k: F.init_fuser(tx, rx, k), k0),
        "gate": jax.eval_shape(lambda k: init_gating(rx, k), k0),
    }
    align = alignment(len(rx.attention_layers), len(tx.attention_layers))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_path_name(p) for p, _ in flat]
    part_rules = {"rx": rules[0], "tx": rules[1]}

    def fill(key):
        leaves = []
        for name, (_, s) in zip(names, flat):
            if name.endswith("/align"):
                leaves.append(jnp.asarray(align, s.dtype))
                continue
            k = jax.random.fold_in(key, zlib.crc32(name.encode()) % 2**31)
            leaves.append(_leaf_value(name, s.shape, s.dtype, k,
                                      part_rules.get(name.split("/")[0], {})))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(fill)(jax.random.PRNGKey(seed % 2**31))


def build_system(rx: ModelConfig, tx: ModelConfig, weights: Dict
                 ) -> FedRefineSystem:
    """The federation: both members, the registry holding the benchmark's
    fuser and gate, the default identity wire and no paraphrase channel."""
    reg = FuserRegistry({rx.name: rx, tx.name: tx})
    reg.fusers[(tx.name, rx.name)] = weights["fuser"]
    reg.gating[rx.name] = weights["gate"]
    members = {rx.name: Participant(rx.name, rx, weights["rx"]),
               tx.name: Participant(tx.name, tx, weights["tx"])}
    return FedRefineSystem(members, reg)


def make_engine(system: FedRefineSystem, rx: ModelConfig, engine: Dict):
    """The receiver's paged, chunked, prefix-cached engine."""
    return system.make_engine(
        rx.name, max_slots=engine["max_slots"], max_seq=engine["max_seq"],
        max_prefix=engine["max_prefix"],
        cache_dtype=DTYPES[engine["cache_dtype"]], paged=True,
        page_size=engine["page_size"], num_pages=engine["num_pages"],
        prefill_token_budget=engine["prefill_token_budget"],
        prefix_cache=engine["prefix_cache"])
