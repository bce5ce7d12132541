"""Whether what the window served is correct.

Once the window has closed and the program's state is freed, a sample of the
requests it finished, drawn from the seed and always holding the longest, is
run through the plain reference (``reference.py``), teacher-forced over each
prompt and the tokens the engine served. The number compared is the widest
gap by which a served token's logit lies below the reference's best, in
units of the RMS of the reference's logits at that position
(``worst_gap_rms``); its limit is the configuration's ``check`` entry. The
sample covers every protocol the window finished, so the C2C transmit side
(transmitter, wire, fuser, gate) and the standalone path (prefix-cache hits,
chunked prefill) are both held to the reference.

Besides, every finished request must carry exactly the tokens it asked for,
and no submit may fail.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench.harness.traffic import Request


def sample(finished: List[Request], seed: int, tokens: int,
           most: int) -> List[Request]:
    """The longest finished request, one of each other protocol, then others
    in an order drawn from the seed, until ``tokens`` served tokens or
    ``most`` requests are in."""
    if not finished:
        return []
    rng = np.random.default_rng([seed, 7])
    order = [finished[i] for i in rng.permutation(len(finished))]
    longest = max(finished, key=lambda r: (r.prompt_len + r.steps, r.index))
    out = [longest]
    for proto in sorted({r.protocol for r in finished}):
        if proto not in {r.protocol for r in out}:
            out.append(next(r for r in order if r.protocol == proto))
    for r in order:
        if sum(x.steps for x in out) >= tokens or len(out) >= most:
            break
        if r not in out:
            out.append(r)
    return out


def compare(reference, chosen: List[Request], control: bool = False
            ) -> Dict[str, float]:
    """Readings of the sample: the worst served gap (and, with
    ``control``, the worst gap of the lower precision's first choices)."""
    worst, worst_ctl, n = 0.0, 0.0, 0
    for r in chosen:
        g = reference.gaps(r.prompt, r.tokens, r.protocol == "c2c",
                           control=control)
        worst = max(worst, float(g["served"].max()))
        n += len(r.tokens)
        if control:
            worst_ctl = max(worst_ctl, float(g["control"].max()))
    out = {"worst_gap_rms": worst, "tokens": n, "requests": len(chosen)}
    if control:
        out["control_worst_gap_rms"] = worst_ctl
    return out


def verdict(gap: float, limit: float, checked: int, problems: List[str],
            failed: List[Request]) -> bool:
    """The run is correct: some requests were checked, the widest gap is
    within its limit, and no request came out short or failed."""
    return checked > 0 and gap <= limit and not problems and not failed


def whole(finished: List[Request]) -> List[str]:
    """Requests that finished with another number of tokens than asked."""
    return [f"request {r.index}: {len(r.tokens)} tokens, asked {r.steps}"
            for r in finished if len(r.tokens) != r.steps]
