"""Server-side fuser registry (paper Fig. 2: "the server maintains all pre-trained
fusers {F_12, F_21, …, F_1N, F_N1}").

Keys are ordered (transmitter, receiver) name pairs; ``ensure_pair`` materialises a
bidirectional link i↔j by creating both F_ij and F_ji (Co-C2C). Checkpointing uses
checkpoint/checkpoint.py so a deployment can restart with its trained fusers.
"""
from __future__ import annotations

import zlib
from typing import Dict, Iterable, Optional, Tuple

import jax

from repro.configs.base import ModelConfig
from repro.core import fuser as F


class FuserRegistry:
    """``seed`` seeds every fuser and gate created without an explicit key:
    each draws from a key folded from the seed and its member names, so a
    registry is the same in every process."""

    def __init__(self, models: Dict[str, ModelConfig], seed: int = 0):
        self.models = dict(models)
        self.seed = seed
        self.fusers: Dict[Tuple[str, str], dict] = {}
        self.gating: Dict[str, dict] = {}  # per receiver

    def _key(self, *names: str) -> jax.Array:
        # a stable digest: Python's str hash is salted per process
        digest = zlib.crc32("\x1f".join(names).encode()) % (2**31)
        return jax.random.fold_in(jax.random.PRNGKey(self.seed), digest)

    # ------------------------------------------------------------- creation
    def ensure_fuser(self, tx: str, rx: str, key=None, **kw) -> dict:
        if (tx, rx) not in self.fusers:
            key = key if key is not None else self._key("fuser", tx, rx)
            self.fusers[(tx, rx)] = F.init_fuser(self.models[tx], self.models[rx],
                                                 key, **kw)
        return self.fusers[(tx, rx)]

    def ensure_pair(self, i: str, j: str, key=None, **kw) -> Tuple[dict, dict]:
        """Bidirectional link i↔j (Co-C2C needs both directions)."""
        return self.ensure_fuser(i, j, key, **kw), self.ensure_fuser(j, i, key, **kw)

    def ensure_all_pairs(self, names: Optional[Iterable[str]] = None, **kw) -> None:
        """Full N·(N−1) fuser matrix of Fig. 2."""
        names = list(names or self.models)
        for i in names:
            for j in names:
                if i != j:
                    try:
                        self.ensure_fuser(i, j, **kw)
                    except F.InapplicableError:
                        pass  # attention-free members simply have no KV links

    def ensure_gating(self, rx: str, key=None) -> dict:
        from repro.core.gating import init_gating
        if rx not in self.gating:
            key = key if key is not None else self._key("gating", rx)
            self.gating[rx] = init_gating(self.models[rx], key)
        return self.gating[rx]

    # ------------------------------------------------------------- access
    def get(self, tx: str, rx: str) -> dict:
        return self.fusers[(tx, rx)]

    def links(self) -> list:
        return sorted(self.fusers)

    # ------------------------------------------------------------- persistence
    def save(self, path: str) -> None:
        from repro.checkpoint.checkpoint import save_pytree
        blob = {
            "fusers": {f"{t}␟{r}": p for (t, r), p in self.fusers.items()},
            "gating": self.gating,
        }
        save_pytree(path, blob)

    def load(self, path: str) -> None:
        from repro.checkpoint.checkpoint import load_pytree
        blob = load_pytree(path)
        self.fusers = {tuple(k.split("␟")): v
                       for k, v in blob.get("fusers", {}).items()}
        self.gating = blob.get("gating", {})
