"""The measured window: the program's own serving path under generated load.

Every request goes through ``FedRefineSystem.submit``; the loop calls the
receiver engine's ``step()`` and nothing else of it. The harness keeps its
own clock: when each request was due, when its submit started and ended, and
the end of every engine step. It reads the engine's public counters
(``stats``), ``first_token_ready`` and the completions ``step()`` returns.

Open loop: requests are due on the mix's schedule, whatever the system does.
Closed loop: each client's next request is due the moment its last one
completed. A request is timed from when it was due, so a stall of the loop
counts against every request that waited behind it.

The load starts ``runup_s`` (traffic key) before the window opens, so the
window finds the system in its steady state: slots filled, requests at every
stage. The run-up is part of the set-up; the window's measures count only
what happened after it opened.
"""
from __future__ import annotations

import contextlib
import time
from collections import deque
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

import jax

from bench.harness.traffic import Mix, Request

#: Engine counters recorded around every step (``engine.stats`` keys).
STEP_COUNTERS = ("prefill_tokens", "prefill_chunks", "radix_matched_tokens",
                 "decode_steps")


@dataclass
class Step:
    t0: float
    t1: float
    delta: Dict[str, int]  # change of STEP_COUNTERS over the step


@dataclass
class Window:
    """What one run-up and measured window served, on the harness clock."""

    w0: float  # the window opens
    w_end: float  # the loop's last action ended
    requests: List[Request]  # every request due since the run-up began
    steps: List[Step]  # every engine step since the run-up began
    stats0: Dict[str, int]
    stats1: Dict[str, int]
    compiles: int = 0  # backend compiles inside the window

    @property
    def seconds(self) -> float:
        return self.w_end - self.w0

    @property
    def k0(self) -> int:
        """Index of the first step that ended inside the window."""
        return next((k for k, s in enumerate(self.steps) if s.t1 > self.w0),
                    len(self.steps))

    def due_in_window(self) -> List[Request]:
        return [r for r in self.requests if r.due_t >= self.w0]


#: warm-up requests submitted before the engine drains them
WARM_GROUP = 8


def _busy(eng) -> bool:
    return bool(eng.num_queued or eng.num_partial or eng.num_active)


class CompileCounter:
    """Counts the backend compiles JAX reports (a persistent-cache read
    counts too: either way the program was not resident)."""

    def __init__(self):
        self.count = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def drain(eng) -> None:
    while _busy(eng):
        eng.step()


def warm_up(system, eng, receiver: str,
            planned: List[Request]) -> Dict[str, float]:
    """Serves one request of each shape of ``planned`` (``Mix.planned``,
    drawn from a warm-up stream): every (protocol, prompt length, shared
    prefix) the run sends, with other token ids. The first request of each protocol
    decodes once; the others are answered by their final prefill chunk.
    This compiles every program and eager operation the window's shapes
    need, the transmit side at each of its prompt lengths included, and
    leaves the shared prefixes in the prefix cache. Returns the requests
    served and the seconds their submits took, per protocol."""
    reqs, shapes = [], set()
    for r in planned:
        if (r.protocol, r.prompt_len, r.prefix) not in shapes:
            shapes.add((r.protocol, r.prompt_len, r.prefix))
            reqs.append(r)
    out: Dict[str, float] = {"requests": len(reqs)}
    for i, r in enumerate(reqs):
        first = f"{r.protocol}_requests" not in out
        out[f"{r.protocol}_requests"] = out.get(f"{r.protocol}_requests",
                                                0) + 1
        t0 = time.perf_counter()
        system.submit(receiver, r.prompt, 2 if first else 1,
                      protocol=r.protocol)
        out[f"{r.protocol}_submit_s"] = out.get(f"{r.protocol}_submit_s",
                                                0.0) + time.perf_counter() - t0
        if i % WARM_GROUP == WARM_GROUP - 1:
            drain(eng)  # a queued C2C request holds its whole fused prefix
    drain(eng)
    return out


def run_window(system, eng, receiver: str, traffic: Dict, mix: Mix,
               seconds: float, *, clock: Callable[[], float] = time.perf_counter,
               compiles: Optional[CompileCounter] = None,
               annotate: bool = False,
               on_open: Optional[Callable[[], None]] = None) -> Window:
    """Drives the engine through the mix's run-up and then for ``seconds``
    more under its load. ``on_open`` runs as the window opens."""
    loop = traffic["loop"]
    runup = float(traffic.get("runup_s", 0.0))

    def span(name: str):
        if not annotate:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(name)

    if loop == "open":
        schedule = mix.schedule(runup, seconds)
        clients = 0
    elif loop == "closed":
        clients = int(traffic["clients"])
        schedule = []
    else:
        raise ValueError(f"traffic loop must be open or closed, got {loop!r}")

    by_rid: Dict[int, Request] = {}
    not_ready: Dict[int, Request] = {}
    due: deque = deque()
    requests: List[Request] = []
    steps: List[Step] = []
    next_idx = 0
    stats0: Dict[str, int] = {}
    t_start = clock()
    w0 = t_start + runup
    w1 = w0 + seconds
    opened = False
    if loop == "open":
        for r in schedule:
            r.due_t = t_start + r.due
            due.append(r)
    else:
        for r in mix.take(clients):
            r.due_t = t_start
            due.append(r)
        next_idx = clients
    requests.extend(due)

    def open_window() -> None:
        nonlocal opened, stats0
        opened = True
        if on_open is not None:
            on_open()
        stats0.update({k: int(eng.stats[k]) for k in STEP_COUNTERS})
        if compiles is not None:
            compiles.count, compiles.on = 0, True

    def submit(r: Request) -> None:
        r.submit_t0 = clock()
        try:
            with span(f"submit.{r.protocol}"):
                r.rid = system.submit(receiver, r.prompt, r.steps,
                                      protocol=r.protocol)
        except Exception as e:  # the loop serves on; the check fails the run
            r.failed = True
            r.error = f"{type(e).__name__}: {e}"[:300]
        r.submit_t1 = clock()
        if r.rid is not None:
            by_rid[r.rid] = r
            not_ready[r.rid] = r

    def next_request(t: float) -> None:
        nonlocal next_idx
        r = mix.take(next_idx + 1)[next_idx]
        next_idx += 1
        r.due_t = t
        due.append(r)
        requests.append(r)

    while True:
        now = clock()
        if now >= w1:
            break
        if not opened and now >= w0:
            open_window()
        while due and due[0].due_t <= now and now < w1:
            r = due.popleft()
            submit(r)
            if r.failed and loop == "closed":
                next_request(r.submit_t1)
            now = clock()
        if now >= w1:
            break
        if not _busy(eng):
            wake = min(due[0].due_t if due else w1, w1)
            if not opened:
                wake = min(wake, w0)
            with span("wait"):
                time.sleep(max(0.0, wake - clock()))
            continue
        before = {k: int(eng.stats[k]) for k in STEP_COUNTERS}
        t0 = clock()
        with span("step"):
            done = eng.step()
        t1 = clock()
        k = len(steps)
        steps.append(Step(t0, t1, {c: int(eng.stats[c]) - before[c]
                                   for c in STEP_COUNTERS}))
        for rid in [rid for rid in not_ready if eng.first_token_ready(rid)]:
            r = not_ready.pop(rid)
            r.ready_step, r.ready_t = k, t1
        for c in done:
            r = by_rid[c.rid]
            not_ready.pop(c.rid, None)
            if r.ready_step is None:
                r.ready_step, r.ready_t = k, t1
            r.done_step, r.done_t, r.tokens = k, t1, c.tokens
            if loop == "closed":
                next_request(t1)
    if not opened:
        open_window()
    w_end = clock()
    if compiles is not None:
        compiles.on = False
    stats1 = {k: int(eng.stats[k]) for k in STEP_COUNTERS}
    return Window(w0, w_end, requests, steps, stats0, stats1,
                  compiles.count if compiles is not None else 0)
