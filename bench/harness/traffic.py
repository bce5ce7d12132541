"""Request streams drawn from a traffic mix's parameters and a seed.

A mix is a data file (``bench/traffic/<name>.json``) that this one generator
reads. Every seed gets the same work in another order: the seed only orders
the requests and draws their token ids.

- Open loop: the run-up and the measured window are each a segment of their
  own. A segment of ``D`` seconds holds ``round(rate_per_s * D)`` requests:
  one set of sizes (each class's share of them, each length at the
  stratified quantiles ``(i + 0.5) / n`` of its clipped lognormal, each
  shared prefix at the quantiles of its Zipf law) on fixed arrival times,
  whose gaps are the stratified quantiles of the exponential law in an order
  that is the mix's own, the same for every seed. So the window holds the
  same requests at the same times for every seed, and only which request
  comes when differs.
- Closed loop: requests come in blocks of ``block``, each holding the same
  set of sizes in a seed-drawn order; the next request goes out when a
  client's last one completed.

Traffic keys (all lengths in tokens):

- ``loop``: ``"open"`` (arrivals on a schedule, ``rate_per_s``) or
  ``"closed"`` (``clients`` each sending the next request when the last one
  completed, no think time, in blocks of ``block`` requests);
- ``runup_s``: seconds of load before the window opens;
- ``classes``: list of ``{"name", "share", "protocol", "prompt", "output"}``
  with ``prompt``/``output`` = ``{"median", "sigma", "min", "max"}`` (and
  an optional ``levels``: the number of distinct lengths a set holds, at
  the stratified quantiles of that many points, each repeated evenly; by
  default every request of the set has its own quantile) and an
  optional ``shared_prefix`` = ``{"count", "tokens", "zipf_s"}``: the prompt
  is then one of ``count`` fixed prefixes followed by the drawn user turn.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np


@dataclass
class Request:
    """One generated request and what the harness records about it."""

    index: int
    klass: str
    protocol: str
    prompt: np.ndarray  # (S,) int32
    steps: int  # output tokens
    due: float = 0.0  # open loop: seconds after the run-up began
    prefix: int = -1  # index of the shared prefix it starts with, or -1
    # filled by the serving loop (harness clock, seconds)
    rid: Optional[int] = None
    due_t: Optional[float] = None
    submit_t0: Optional[float] = None
    submit_t1: Optional[float] = None
    ready_step: Optional[int] = None
    ready_t: Optional[float] = None
    done_step: Optional[int] = None
    done_t: Optional[float] = None
    tokens: Optional[np.ndarray] = None
    failed: bool = False
    error: Optional[str] = None  # why its submit failed

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])


def lognormal_quantiles(spec: Dict, n: int) -> np.ndarray:
    """``n`` lengths at the stratified quantiles of a lognormal with the
    given median and sigma, rounded and clipped to ``[min, max]``; with
    ``levels`` k in ``spec``, the quantiles of k points, each taken by n / k
    of the n lengths (rounded down or up)."""
    k = int(spec.get("levels", n))
    z = np.array([NormalDist().inv_cdf((i + 0.5) / k) for i in range(k)])
    x = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    x = np.clip(x, spec["min"], spec["max"]).astype(np.int64)
    return x[(np.arange(n) * k) // n]


def zipf_quantiles(count: int, s: float, n: int) -> np.ndarray:
    """``n`` ranks in ``[0, count)`` at the stratified quantiles of a Zipf
    law with exponent ``s`` (rank k has weight ``(k + 1) ** -s``)."""
    w = (np.arange(count) + 1.0) ** -s
    cdf = np.cumsum(w / w.sum())
    u = (np.arange(n) + 0.5) / n
    return np.minimum(np.searchsorted(cdf, u), count - 1)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps at the stratified quantiles of an exponential
    law, scaled so that their mean is exactly ``1 / rate``."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u)
    return g / g.mean() / rate


#: the order of every open-loop segment's arrival gaps: the mix's own, the
#: same for every seed
ARRIVAL_ORDER = 0


def arrival_times(rate: float, start: float, seconds: float) -> np.ndarray:
    """The arrivals of one open-loop segment ``[start, start + seconds)``:
    ``round(rate * seconds)`` of them, the first at ``start``, their gaps the
    stratified quantiles of the exponential law in a fixed order, scaled so
    that the segment's gaps (the last one leading into the next segment)
    span its ``seconds``."""
    n = int(math.floor(rate * seconds + 0.5))
    if n == 0:
        return np.zeros(0)
    g = np.random.default_rng(ARRIVAL_ORDER).permutation(
        exponential_gaps(rate, n))
    g = g * (seconds / g.sum())
    return start + np.concatenate([[0.0], np.cumsum(g[:-1])])


def class_counts(classes: List[Dict], n: int) -> List[int]:
    """Requests per class in a set of ``n``: shares rounded by largest
    remainder, so the counts sum to ``n``."""
    raw = [c["share"] * n for c in classes]
    counts = [int(math.floor(r)) for r in raw]
    order = sorted(range(len(raw)), key=lambda i: counts[i] - raw[i])
    for i in order[: n - sum(counts)]:
        counts[i] += 1
    return counts


class Mix:
    """The request stream of one traffic mix for one seed.

    ``stream`` 0 is the measured run; other streams (warm-up) give the same
    sizes with other token ids and order. The shared prefixes depend on the
    seed alone, so a warm-up stream fills the prefix cache with the very
    prefixes the window sends."""

    def __init__(self, traffic: Dict, vocab: int, seed: int, stream: int = 0,
                 rate: Optional[float] = None):
        self.traffic = traffic
        self.vocab = vocab
        self.seed, self.stream = seed, stream
        self.block = int(traffic.get("block", 0))
        self.classes = traffic["classes"]
        self.rate = rate if rate is not None else traffic.get("rate_per_s")
        base = np.random.default_rng([seed, 0])
        self.prefixes: Dict[str, np.ndarray] = {}
        for c in self.classes:
            sp = c.get("shared_prefix")
            if sp:
                self.prefixes[c["name"]] = base.integers(
                    0, vocab, (sp["count"], sp["tokens"]), dtype=np.int32)
        self.rng = np.random.default_rng([seed, 1 + stream])
        self._made: List[Request] = []

    def _draw(self, rng, dues: np.ndarray, first: int) -> List[Request]:
        """One set of ``len(dues)`` requests: each class's share of them,
        their lengths and prefixes at stratified quantiles, in an order drawn
        from ``rng``, due at ``dues`` in turn."""
        n = len(dues)
        slots: List[Dict] = []
        for c, k in zip(self.classes, class_counts(self.classes, n)):
            if k == 0:
                continue
            prompts = rng.permutation(lognormal_quantiles(c["prompt"], k))
            outputs = rng.permutation(lognormal_quantiles(c["output"], k))
            sp = c.get("shared_prefix")
            ranks = (rng.permutation(zipf_quantiles(sp["count"], sp["zipf_s"],
                                                    k))
                     if sp else np.full(k, -1))
            for p, o, r in zip(prompts, outputs, ranks):
                slots.append({"c": c, "p": int(p), "o": int(o), "k": int(r)})
        out = []
        for j, due in zip(rng.permutation(n), dues):
            s = slots[j]
            c = s["c"]
            own = rng.integers(0, self.vocab, s["p"], dtype=np.int32)
            if s["k"] >= 0:
                prompt = np.concatenate([self.prefixes[c["name"]][s["k"]], own])
            else:
                prompt = own
            out.append(Request(index=first + len(out), klass=c["name"],
                               protocol=c["protocol"], prompt=prompt,
                               steps=s["o"], due=float(due), prefix=s["k"]))
        return out

    def schedule(self, runup: float, seconds: float) -> List[Request]:
        """Open loop: every request due in the run-up ``[0, runup)`` and in
        the window ``[runup, runup + seconds)``, in due order; each segment
        is one set of sizes on its fixed arrival times."""
        rng = np.random.default_rng([self.seed, 1 + self.stream])
        out = self._draw(rng, arrival_times(self.rate, 0.0, runup), 0)
        return out + self._draw(
            rng, arrival_times(self.rate, runup, seconds), len(out))

    def take(self, n: int) -> List[Request]:
        """Closed loop: the first ``n`` requests of the stream, drawn block
        by block."""
        while len(self._made) < n:
            self._made.extend(self._draw(self.rng, np.zeros(self.block),
                                         len(self._made)))
        return self._made[:n]

    def planned(self, seconds: float) -> List[Request]:
        """Requests that hold every shape a run of ``seconds`` sends: the
        open loop's whole schedule, or one closed-loop block."""
        if self.traffic["loop"] == "open":
            return self.schedule(float(self.traffic.get("runup_s", 0.0)),
                                 seconds)
        return self.take(self.block)

    def longest(self) -> int:
        """Largest prompt plus output this mix can send, in tokens."""
        return max(
            (c.get("shared_prefix") or {}).get("tokens", 0)
            + c["prompt"]["max"] + c["output"]["max"] for c in self.classes)
