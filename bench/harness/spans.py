"""The program's own spans on the trace's clock, reduced per span name.

The program marks its layers with ``jax.profiler.TraceAnnotation`` spans
named ``fr.<part>`` (``core/fedrefine.py``, ``launch/engine.py``,
``models/cache.py``), on the host thread that drives it: the same ``python3``
line of the ``/host:CPU`` plane as the harness's own spans (``trace.py``).
A compile is marked by the lowering before it: JAX lowers a program each time
it is not resident (``lower_sharding_computation``), then compiles it
(``backend_compile_and_load``, after the lowering) or reads it from the
persistent compile cache, which on a TPU leaves no host event of its own. So
the lowering is the one event every compile that ``jax.monitoring`` counts
leaves on the line, once.

``reduce`` reads events in ``trace.collect``'s form, the program's spans with
their stats (``"stats": dict(event.stats)``) and the compile events among
them, and works over the harness's ``window`` span. It gives, per name:

- ``count`` and ``total_s``: spans that started in the window, and their
  summed duration;
- ``self_s``: the duration minus the part its child ``fr.`` spans cover;
- ``idle_s``: device-idle time during which this event was the innermost
  host event open (a program span or a compile);
- ``idle_in_s``: device-idle time while the host was inside it at any depth;
- ``compiles`` and ``compiles_in``: compile events whose innermost program
  span is this one, or that lie under it at any depth;
- ``tags``: how many spans carried each string stat (``protocol=c2c``).

Device-idle time is the window less the union of the device's operations,
averaged over the chips traced, as ``trace.reduce`` counts ``busy_s``.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional

PREFIX = "fr."
COMPILE_EVENTS = ("lower_sharding_computation",)


def _idle(events: List[Dict], w0: float, w1: float) -> List[tuple]:
    """Per device, the intervals of the window no operation covered."""
    gaps = []
    for dev in sorted({e["plane"] for e in events
                       if e["plane"].startswith("/device:")}):
        ops = sorted((max(e["start_ns"], w0),
                      min(e["start_ns"] + e["dur_ns"], w1)) for e in events
                     if e["plane"] == dev and e["line"] == "XLA Ops"
                     and w0 <= e["start_ns"] < w1)
        t = w0
        for a, b in ops:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if w1 > t:
            gaps.append((t, w1))
    return sorted(gaps)


def reduce(events: List[Dict]) -> Dict:
    """``{"window_s", "devices", "spans": {name: {...}}, "idle_other_s"}``
    over one traced window (see the module's docstring); ``idle_other_s`` is
    device-idle time under no program span or compile."""
    win = [e for e in events if e["line"] == "python3"
           and e["name"] == "window"]
    if not win:
        raise ValueError("the trace holds no 'window' span")
    w0 = win[0]["start_ns"]
    w1 = w0 + win[0]["dur_ns"]
    devices = len({e["plane"] for e in events
                   if e["plane"].startswith("/device:")})
    host = sorted((e for e in events if e["line"] == "python3"
                   and (e["name"].startswith(PREFIX)
                        or e["name"] in COMPILE_EVENTS)
                   and w0 <= e["start_ns"] < w1),
                  key=lambda e: (e["start_ns"], -e["dur_ns"]))
    end = [e["start_ns"] + e["dur_ns"] for e in host]
    parent: List[Optional[int]] = [None] * len(host)
    child_ns = [0.0] * len(host)
    idle_ns = [0.0] * len(host)
    # partition the window by the innermost host event open, and lay the
    # device's idle intervals over that partition
    pieces: List[tuple] = []
    stack: List[int] = []
    t = w0

    def close_to(x: float, who: Optional[int]) -> None:
        nonlocal t
        x = min(x, w1)
        if x > t:
            pieces.append((t, x, who))
            t = x

    for i, e in enumerate(host):
        while stack and end[stack[-1]] <= e["start_ns"]:
            close_to(end[stack[-1]], stack[-1])
            stack.pop()
        close_to(e["start_ns"], stack[-1] if stack else None)
        if stack:
            parent[i] = stack[-1]
            if e["name"].startswith(PREFIX):
                child_ns[stack[-1]] += e["dur_ns"]
        stack.append(i)
    while stack:
        close_to(end[stack[-1]], stack[-1])
        stack.pop()
    close_to(w1, None)
    other_ns = 0.0
    gaps = _idle(events, w0, w1)
    k = 0
    for a, b, who in pieces:
        while k < len(gaps) and gaps[k][1] <= a:
            k += 1
        j = k
        while j < len(gaps) and gaps[j][0] < b:
            # with several devices, a later gap may end before the piece
            ns = max(0.0, min(b, gaps[j][1]) - max(a, gaps[j][0]))
            if who is None:
                other_ns += ns
            else:
                idle_ns[who] += ns
            j += 1

    def program_parent(i: int) -> Optional[int]:
        p = parent[i]
        while p is not None and not host[p]["name"].startswith(PREFIX):
            p = parent[p]
        return p

    per = max(devices, 1)
    out: Dict[str, Dict] = defaultdict(lambda: {
        "count": 0, "total_s": 0.0, "self_s": 0.0, "idle_s": 0.0,
        "idle_in_s": 0.0, "compiles": 0, "compiles_in": 0,
        "tags": defaultdict(int)})
    for i, e in enumerate(host):
        s = out[e["name"]]
        s["count"] += 1
        s["total_s"] += e["dur_ns"] * 1e-9
        s["self_s"] += (e["dur_ns"] - child_ns[i]) * 1e-9
        s["idle_s"] += idle_ns[i] * 1e-9 / per
        for key, v in e.get("stats", {}).items():
            if isinstance(v, str):
                s["tags"][f"{key}={v}"] += 1
        # ancestors' names, each once, for the inclusive sums
        names, p = {e["name"]}, parent[i]
        while p is not None:
            names.add(host[p]["name"])
            p = parent[p]
        for n in names:
            out[n]["idle_in_s"] += idle_ns[i] * 1e-9 / per
        if e["name"] in COMPILE_EVENTS:
            p = program_parent(i)
            if p is not None:
                out[host[p]["name"]]["compiles"] += 1
            for n in names - {e["name"]}:
                out[n]["compiles_in"] += 1
    return {"window_s": (w1 - w0) * 1e-9, "devices": devices,
            "idle_other_s": other_ns * 1e-9 / per,
            "spans": {n: dict(s, tags=dict(s["tags"]))
                      for n, s in out.items()}}


def describe(program: Dict) -> List[str]:
    """One line per span name, longest total first."""
    rows = sorted(program["spans"].items(), key=lambda kv: -kv[1]["total_s"])
    return [f"span {n}: {s['count']} x, {s['total_s']:.4f} s total, "
            f"{s['self_s']:.4f} s self, {s['idle_s']:.4f} s device idle "
            f"({s['idle_in_s']:.4f} s inside), {s['compiles']} compiles "
            f"({s['compiles_in']} inside)" for n, s in rows]


# ------------------------------------------------- per-layer readings
# Each reads a run (``cell.Run``) whose ``trace`` holds this module's
# ``reduce`` under ``"program"``, and returns None where it finds nothing to
# read: a program without these spans or counters, or an untraced run.


def _spans(run) -> Optional[Dict]:
    prog = (run.trace or {}).get("program")
    return prog["spans"] if prog and prog["spans"] else None


def _c2c_submits(spans: Dict) -> int:
    return spans.get("fr.submit", {}).get("tags", {}).get("protocol=c2c", 0)


def _per_c2c_submit_ms(run, name: str) -> Optional[float]:
    spans = _spans(run)
    if spans is None or name not in spans or not _c2c_submits(spans):
        return None
    return spans[name]["total_s"] / _c2c_submits(spans) * 1e3


def tx_prefill_ms(run) -> Optional[float]:
    """Mean ``fr.tx_prefill`` time per C2C submit started in the window."""
    return _per_c2c_submit_ms(run, "fr.tx_prefill")


def digest_ms(run) -> Optional[float]:
    """Mean ``fr.digest`` time per C2C submit started in the window."""
    return _per_c2c_submit_ms(run, "fr.digest")


def submit_compiles(run) -> Optional[float]:
    """Compile events in the window under ``fr.submit``."""
    spans = _spans(run)
    if spans is None or "fr.submit" not in spans:
        return None
    return spans["fr.submit"]["compiles_in"]


def step_host_ms(run) -> Optional[float]:
    """Mean over ``fr.step`` of its duration less its ``fr.sync``."""
    spans = _spans(run)
    if spans is None or "fr.step" not in spans:
        return None
    step = spans["fr.step"]
    sync = spans.get("fr.sync", {}).get("total_s", 0.0)
    return (step["total_s"] - sync) / step["count"] * 1e3


def _idle_share(run, name: str) -> Optional[float]:
    spans = _spans(run)
    if spans is None or name not in spans:
        return None
    return 100.0 * spans[name]["idle_in_s"] / run.trace["program"]["window_s"]


def submit_idle_share(run) -> Optional[float]:
    """Device-idle share of the window while the host is inside
    ``fr.submit`` (%)."""
    return _idle_share(run, "fr.submit")


def step_idle_share(run) -> Optional[float]:
    """Device-idle share of the window while the host is inside ``fr.step``
    (%)."""
    return _idle_share(run, "fr.step")


def queue_wait_ms(run) -> Optional[float]:
    """Mean wait in the engine's queue of the requests that left it in the
    window (ms), from the engine's ``queue_wait_us`` and ``reserved``."""
    w = run.window
    if "reserved" not in w.stats0 or "reserved" not in w.stats1:
        return None
    n = w.stats1["reserved"] - w.stats0["reserved"]
    if n <= 0:
        return None
    return (w.stats1["queue_wait_us"] - w.stats0["queue_wait_us"]) / n / 1e3


#: metric name -> reading, for the readers in ``bench/metrics/``
READINGS = {
    "tx_prefill_ms.c2c": tx_prefill_ms,
    "digest_ms.c2c": digest_ms,
    "window_compiles.submit": submit_compiles,
    "step_host_ms.serve": step_host_ms,
    "queue_wait_ms": queue_wait_ms,
    "idle_share.submit": submit_idle_share,
    "idle_share.step": step_idle_share,
}
