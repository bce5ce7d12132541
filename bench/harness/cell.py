"""One run of one cell: build, warm up, measure, check, report."""
from __future__ import annotations

import gc
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from bench.harness import check, model, serve, trace as tr
from bench.harness.reference import Reference
from bench.harness.spec import Cell, Spec
from bench.harness.traffic import Mix
from bench.harness.work import Dims, peaks


class NoChip(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell asks for."""


@dataclass
class Run:
    """What a metric reader reads."""

    cell: Cell
    window: serve.Window
    setup_s: float
    rx: Dims
    tx: Dims
    engine: Dict
    itemsize: int
    peak: Optional[Dict]
    trace: Optional[Dict]


def chip(cell: Cell):
    """The devices this run may use; raises NoChip off a TPU."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, found {devices[0].platform} "
                     f"({devices[0].device_kind})")
    if len(devices) < cell.chips:
        raise NoChip(f"cell {cell.name} needs {cell.chips} chips, found "
                     f"{len(devices)}")
    from repro.kernels import ops

    if ops._interpret():
        raise NoChip("the Pallas kernels would run in interpret mode")
    return devices


def run_cell(spec: Spec, name: str, seed: int, seconds: float, trace: bool,
             *, t_start: float, require_chip: bool = True,
             rate: Optional[float] = None, control: bool = False,
             engine_hook=None, log=None) -> Dict:
    """Runs cell ``name`` once and returns its result line (a dict).
    ``engine_hook(engine)`` runs once the engine is built (tests plant
    faults in the served path through it). With ``control`` the check
    judges the control in the served path's place: at each position of the
    same prompts and served tokens, the token that the reference computed
    one precision lower puts first; ``correct`` is then the control's
    verdict, and ``served_correct`` the served path's."""
    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    cell = spec.cell(name)
    devices = chip(cell) if require_chip else jax.devices()
    dev = devices[0]
    peak = peaks(dev.device_kind) if require_chip else None
    conf, traffic = cell.config, cell.traffic
    archs = [spec.architecture(conf[s], "arch") for s in model.SIDES]
    refs = [spec.architecture(conf[s], "ref") for s in model.SIDES]
    rx_cfg, tx_cfg = model.configs(conf, archs)
    dtype = model.DTYPES[conf["dtype"]]

    t0 = time.perf_counter()
    weights = jax.block_until_ready(model.make_weights(
        rx_cfg, tx_cfg, seed, dtype, rules=[a.WEIGHT_RULES for a in archs]))
    system = model.build_system(rx_cfg, tx_cfg, weights)
    eng = model.make_engine(system, rx_cfg, conf["engine"])
    if engine_hook is not None:
        engine_hook(eng)
    t1 = time.perf_counter()
    warm = serve.warm_up(system, eng, rx_cfg.name,
                         Mix(traffic, rx_cfg.vocab_size, seed, stream=1,
                             rate=rate).planned(seconds))
    mix = Mix(traffic, rx_cfg.vocab_size, seed, stream=0, rate=rate)
    compiles = serve.CompileCounter()
    t2 = time.perf_counter()
    log(f"set-up: {t0 - t_start:.1f} s to start, {t1 - t0:.1f} s weights "
        f"and engine, {t2 - t1:.1f} s warm-up ({int(warm['requests'])} "
        f"requests; C2C submits at {int(warm.get('c2c_requests', 0))} "
        f"prompt lengths {warm.get('c2c_submit_s', 0.0):.1f} s); the "
        f"run-up follows")

    trace_dir = os.path.join(spec.root, "bench_out", f"trace-{name}")
    summary = None
    spans = []

    def open_trace():
        shutil.rmtree(trace_dir, ignore_errors=True)
        tr.start(trace_dir)
        # made once the profiler runs, or the span is not recorded
        spans.append(jax.profiler.TraceAnnotation("window"))
        spans[0].__enter__()

    window = serve.run_window(system, eng, rx_cfg.name, traffic, mix,
                              seconds, compiles=compiles, annotate=trace,
                              on_open=open_trace if trace else None)
    if trace:
        spans[0].__exit__(None, None, None)
        tr.stop()
        summary = tr.reduce(tr.collect(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    setup_s = window.w0 - t_start
    stats = dev.memory_stats() or {}
    mem_peak = stats.get("peak_bytes_in_use")
    due = window.due_in_window()
    log(f"window: {len(due)} requests due, {len(window.steps) - window.k0} "
        f"steps, {window.compiles} compiles")

    # the program's state goes before the reference runs
    del eng
    system.engines.clear()
    del system
    gc.collect()

    finished = [r for r in window.requests
                if r.tokens is not None and r.done_t >= window.w0]
    chk = conf["check"]
    chosen = check.sample(finished, seed, chk["sample_tokens"],
                          chk["sample_requests"])
    ref = Reference(conf, weights, mix.longest(), max(
        [c["prompt"]["max"] for c in traffic["classes"]
         if c["protocol"] == "c2c"] or [16]), refs)
    t0 = time.perf_counter()
    readings = check.compare(ref, chosen, control=control)
    log(f"reference: {readings['requests']} requests, {readings['tokens']} "
        f"tokens in {time.perf_counter() - t0:.1f} s")
    problems = check.whole(finished)
    failed = [r for r in window.requests if r.failed]
    limit = float(chk["worst_gap_rms"])
    # the control's first choices stand in for the served tokens, and are
    # judged by the same rule
    judged = "control_worst_gap_rms" if control else "worst_gap_rms"
    correct = check.verdict(readings[judged], limit, len(chosen), problems,
                            failed)

    run = Run(cell, window, setup_s, refs[0].dims(conf["receiver"]),
              refs[1].dims(conf["transmitter"]), conf["engine"],
              jnp.dtype(model.DTYPES[conf["engine"]["cache_dtype"]]).itemsize,
              peak, summary)
    metrics = {}
    for m in cell.metrics:
        if m.end_to_end == trace:
            continue
        v = spec.reader(m.name)(run)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    result = {"correct": correct, "attempted": len(due),
              "failed": sum(r.failed for r in due),
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    checked = {"worst_gap_rms": {"value": readings[judged], "limit": limit},
               "requests_checked": {"value": readings["requests"],
                                    "limit": 1},
               "short_requests": {"value": len(problems), "limit": 0},
               "failed_requests": {"value": len(failed), "limit": 0}}
    if control:
        checked["served_worst_gap_rms"] = {
            "value": readings["worst_gap_rms"], "limit": limit}
        result["served_correct"] = check.verdict(
            readings["worst_gap_rms"], limit, len(chosen), problems, failed)
    result["check"] = checked
    for what in problems[:5] + [f"request {r.index} failed: {r.error}"
                                for r in failed[:5]]:
        log(what)
    for k, v in checked.items():
        log(f"check {k} {v['value']} limit {v['limit']}")
    return result


def print_result(result: Dict) -> None:
    print(json.dumps(result), flush=True)
