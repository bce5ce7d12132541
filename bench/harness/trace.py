"""The device trace of a window and its reduction to per-layer numbers.

The profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData`` reads
it. ``collect`` keeps what the reduction needs as plain events (plane, line,
name, start and duration in ns), so the reduction can be checked on a small
recorded fixture. On a TPU the device plane is ``/device:TPU:<n>``, its
programs are on the ``XLA Modules`` line (``jit_decode(<fingerprint>)``) and
its operations on ``XLA Ops`` (``%<op>.<k> = <shape> ...``); a Pallas kernel
is an operation named after the jitted function that calls it
(``%paged_decode_attention_pallas.11``). The harness's own spans (``window``,
``step``, ``submit.<protocol>``, ``wait``) are on the host's ``python3``
line.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List

import jax

HOST_SPANS = ("step", "submit.c2c", "submit.standalone", "wait")
#: operations that only contain others (the layer scan's loop)
CONTAINERS = ("while", "conditional", "call")


def start(trace_dir: str) -> None:
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # keep the host's own spans, not every call
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def stop() -> None:
    jax.profiler.stop_trace()


def collect(trace_dir: str) -> List[Dict]:
    """The events the reduction reads, from the newest trace under
    ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if device and line.name in ("XLA Ops", "XLA Modules"):
                pass
            elif plane.name == "/host:CPU" and line.name == "python3":
                pass
            else:
                continue
            for e in line.events:
                if not device and e.name not in HOST_SPANS + ("window",):
                    continue
                out.append({"plane": plane.name, "line": line.name,
                            "name": e.name, "start_ns": float(e.start_ns),
                            "dur_ns": float(e.duration_ns)})
    return out


def op_name(event_name: str) -> str:
    """``%copy_dynamic-update-slice_fusion.5 = bf16[...] ...`` ->
    ``copy_dynamic-update-slice_fusion``; ``jit_decode(123)`` ->
    ``jit_decode``."""
    name = event_name.split(" = ", 1)[0].lstrip("%")
    name = re.sub(r"\(\d+\)$", "", name)
    return re.sub(r"(\.(\d+|clone\d*))+$", "", name)


def _union(intervals: List[tuple]) -> List[tuple]:
    merged: List[list] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def reduce(events: List[Dict]) -> Dict:
    """Per-layer numbers of one traced window:

    - ``window_s``: the harness's ``window`` span;
    - ``busy_s``: union of the device's operation intervals in the window,
      averaged over the chips traced;
    - ``modules``: program -> list of device seconds per execution;
    - ``kernels``: operation -> (calls, device seconds), with the program it
      ran in, ``<program>/<op>``;
    - ``device_ops``: the ten operations that took most device time
      (loop containers left out, as their bodies are counted);
    - ``idle_gaps``: the ten longest device idle gaps, each named by the
      harness span the host was in at the gap's middle."""
    win = [e for e in events if e["line"] == "python3"
           and e["name"] == "window"]
    if not win:
        raise ValueError("the trace holds no 'window' span")
    w0 = win[0]["start_ns"]
    w1 = w0 + win[0]["dur_ns"]
    devices = sorted({e["plane"] for e in events
                      if e["plane"].startswith("/device:")})
    busy = 0.0
    gaps: List[tuple] = []
    modules: Dict[str, List[float]] = defaultdict(list)
    kernels: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    op_time: Dict[str, float] = defaultdict(float)
    host = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"], e["name"])
                  for e in events if e["line"] == "python3"
                  and e["name"] in HOST_SPANS)
    for dev in devices:
        mods = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"],
                       op_name(e["name"])) for e in events
                      if e["plane"] == dev and e["line"] == "XLA Modules"
                      and w0 <= e["start_ns"] < w1)
        for a, b, m in mods:
            modules[m].append((b - a) * 1e-9)
        ops = [e for e in events if e["plane"] == dev
               and e["line"] == "XLA Ops" and w0 <= e["start_ns"] < w1]
        spans = []
        mi = 0
        for e in sorted(ops, key=lambda e: e["start_ns"]):
            a, b = e["start_ns"], e["start_ns"] + e["dur_ns"]
            spans.append((max(a, w0), min(b, w1)))
            name = op_name(e["name"])
            while mi < len(mods) and mods[mi][1] < a:
                mi += 1
            prog = mods[mi][2] if mi < len(mods) and mods[mi][0] <= a \
                else "?"
            k = kernels[f"{prog}/{name}"]
            k[0] += 1
            k[1] += e["dur_ns"] * 1e-9
            if not name.startswith(CONTAINERS):
                op_time[name] += e["dur_ns"] * 1e-9
        merged = _union(spans)
        busy += sum(b - a for a, b in merged) * 1e-9
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for i in range(0, len(edges), 2):
            a, b = edges[i], edges[i + 1]
            if b > a:
                gaps.append((a, b))
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for a, b in gaps[:10]:
        mid = (a + b) / 2
        what = "other"
        for s0, s1, name in host:
            if s0 <= mid < s1:
                what = name
            if s0 > mid:
                break
        named.append([what, (b - a) * 1e-9])
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy / max(len(devices), 1),
        "devices": len(devices),
        "modules": dict(modules),
        "kernels": {k: (v[0], v[1]) for k, v in kernels.items()},
        "device_ops": [[n, t] for n, t in top],
        "idle_gaps": named,
    }


def kernel_seconds(summary: Dict, program: str, op: str) -> tuple:
    """(calls, device seconds) of operation ``op`` inside ``program``."""
    return summary["kernels"].get(f"{program}/{op}", (0, 0.0))
