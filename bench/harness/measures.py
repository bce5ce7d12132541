"""End-to-end and host-side measures of one window, on the harness clock.

- TTFT: from when a request was due until the end of the engine step after
  which ``first_token_ready`` held. A request with no first token when the
  window closed enters with its wait so far.
- Inter-token gaps: a request that turns ready in step k received its first
  token from that step's final prefill chunk and its second from the same
  step's decode, then one token from every later step until it completed. Its
  gaps are the times between the ends of those steps; one still active when
  the window closed adds its open gap up to the close.
- Tokens delivered: the same accounting, summed over requests.

Only what happened in the window counts: requests due after it opened (for
TTFT), gaps and tokens of steps that ended after it opened (for all requests,
those of the run-up included).
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

from bench.harness.serve import Window
from bench.harness.traffic import Request


def percentile(values, q: float) -> Optional[float]:
    """The q-th percentile (numpy's linear interpolation); None if empty."""
    v = np.asarray(values, np.float64)
    return float(np.percentile(v, q)) if v.size else None


def ttft_s(w: Window) -> List[float]:
    out = []
    for r in w.due_in_window():
        end = r.ready_t if r.ready_t is not None else w.w_end
        out.append(end - r.due_t)
    return out


def last_step(w: Window, r: Request) -> int:
    """The last step in which ``r`` received a token, within the window."""
    return r.done_step if r.done_step is not None else len(w.steps) - 1


def delivered(w: Window, r: Request) -> int:
    """Output tokens ``r`` received in the window's steps."""
    if r.ready_step is None:
        return 0
    last, k0 = last_step(w, r), w.k0
    if r.ready_step >= k0:
        return last - r.ready_step + 2
    return max(0, last - k0 + 1)


def itl_s(w: Window) -> List[float]:
    ends = np.array([s.t1 for s in w.steps])
    k0 = w.k0
    gaps: List[float] = []
    for r in w.requests:
        if r.ready_step is None:
            continue
        last = last_step(w, r)
        first = max(r.ready_step + 1, k0)
        if last >= first:
            gaps.extend((ends[first:last + 1] - ends[first - 1:last])
                        .tolist())
        if r.done_step is None:
            gaps.append(w.w_end - ends[last])
    return gaps


def tokens(w: Window) -> int:
    return sum(delivered(w, r) for r in w.requests)


def window_steps(w: Window):
    """(k, step) of the decode steps that ended inside the window."""
    return [(k, s) for k, s in enumerate(w.steps)
            if k >= w.k0 and s.delta["decode_steps"]]


def active_at(w: Window, k: int) -> List[Request]:
    """Requests decoded in step k."""
    return [r for r in w.requests if r.ready_step is not None
            and r.ready_step <= k <= last_step(w, r)]


def live_keys(r: Request, k: int) -> int:
    """Keys of ``r``'s own cache the decode in step k attends over: the
    activation step's decode writes position S, so step k attends
    S + (k - ready_step) + 1 positions."""
    return r.prompt_len + (k - r.ready_step) + 1


def prefill_chunks(w: Window):
    """(step, request, first row, rows) of every chunk of prompt the window
    prefilled, or None where the engine's own counters disown them.

    Rebuilt from each step's ``prefill_tokens``: the budget goes to
    submitted prompts first in, first out, starting at row 0. The rebuild is
    held to what the engine reports, so that a change of its scheduling
    silences the readers instead of moving them: each step must count as
    many chunks as the engine's ``prefill_chunks`` counter, every prompt
    must end its prefill in the step after which the engine reported its
    first token ready, and no prompt may be served from the prefix cache
    (a matched prefix is not prefilled from row 0)."""
    if any(s.delta["radix_matched_tokens"] for s in w.steps):
        return None
    fifo = [r for r in sorted(w.requests, key=lambda r: r.submit_t0 or 0)
            if r.rid is not None]
    done = {id(r): 0 for r in fifo}
    head = 0
    out = []
    for k, s in enumerate(w.steps):
        n = s.delta["prefill_tokens"]
        chunks = 0
        while n > 0 and head < len(fifo):
            r = fifo[head]
            m = min(n, r.prompt_len - done[id(r)])
            if m > 0:
                out.append((k, r, done[id(r)], m))
                chunks += 1
                done[id(r)] += m
                n -= m
            if done[id(r)] >= r.prompt_len:
                if r.ready_step != k:
                    return None
                head += 1
        if n or chunks != s.delta["prefill_chunks"]:
            return None
    return out


# ------------------------------------------------------- per-layer readings


def mean_step_ms(run) -> Optional[float]:
    s = run.window.steps[run.window.k0:]
    return sum(x.t1 - x.t0 for x in s) / len(s) * 1e3 if s else None


def program_ms(run, program: str) -> Optional[float]:
    """Mean device time of one execution of ``program`` in the trace."""
    if run.trace is None:
        return None
    t = run.trace["modules"].get(program)
    return sum(t) / len(t) * 1e3 if t else None


def idle_share(run) -> Optional[float]:
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])


def _prefix(r: Request) -> int:
    """Fused positions a C2C request attends (its transmitter saw the same
    prompt); 0 otherwise."""
    return r.prompt_len if r.protocol == "c2c" else 0


def served_flops(run) -> Optional[float]:
    """FLOPs the window's served work needs (see ``work.py``): the transmit
    side of every C2C request submitted in the window; the whole prompt of
    every request whose first token came in the window (a prompt's causal
    work is the same however it is chunked; one whose prefill began before
    the window opened counts whole, at most one budget of rows), with its
    first token's logits; every token decoded in the window. None where the
    window served a prompt from the prefix cache, whose matched rows are
    not prefilled."""
    from bench.harness.work import transmit_flops

    w, rx = run.window, run.rx
    if w.stats1["radix_matched_tokens"] != w.stats0["radix_matched_tokens"]:
        return None
    total = 0.0
    for r in w.requests:
        if r.protocol == "c2c" and r.rid is not None \
                and r.submit_t0 >= w.w0:
            total += transmit_flops(run.tx, rx, r.prompt_len)
        if r.ready_step is not None and r.ready_step >= w.k0:
            total += rx.prefill_flops(0, r.prompt_len, _prefix(r)) \
                + rx.head_flops()
    for k, _ in window_steps(w):
        for r in active_at(w, k):
            total += rx.decode_token_flops(live_keys(r, k), _prefix(r))
    return total


def paged_decode_need_s(run) -> float:
    """Least device time the paged decode kernel's calls need: one call per
    layer per decode step, over the live pages of the slots it served."""
    from bench.harness.work import need_seconds, paged_decode_need

    w, rx, e = run.window, run.rx, run.engine
    total = 0.0
    for k, _ in window_steps(w):
        flops = nbytes = 0.0
        for r in active_at(w, k):
            f, b = paged_decode_need(rx, live_keys(r, k), e["page_size"],
                                     run.itemsize)
            flops += f
            nbytes += b
        total += rx.layers * need_seconds(flops, nbytes, run.peak)
    return total


def ragged_prefill_need_s(run) -> Optional[float]:
    """Least device time the ragged prefill kernel's calls need: one call
    per layer per chunk; None where the chunks cannot be told."""
    from bench.harness.work import need_seconds, ragged_prefill_need

    rx, e = run.rx, run.engine
    chunks = prefill_chunks(run.window)
    if chunks is None:
        return None
    total = 0.0
    for k, r, start, rows in chunks:
        if k < run.window.k0:
            continue
        f, b = ragged_prefill_need(rx, start, rows, e["page_size"],
                                   run.itemsize)
        total += rx.layers * need_seconds(f, b, run.peak)
    return total
