"""Finds the rate an open-loop cell sustains: one process serves the cell's
mix at each rate in turn, each on a fresh engine after a warm-up, and
prints one JSON line per rate (offered and completed requests per second,
TTFT median and 95th percentile, requests still waiting for their first
token at the close). The knee is the highest rate at which the system keeps
up; the cell's traffic file then offers four fifths of it.

    python3 bench/sweep.py --workload chat-mix --seed 1 --seconds 51 \
        --rates 0.3,0.45,0.6,0.75
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402

jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

from bench.harness import measures, model, serve  # noqa: E402
from bench.harness.cell import chip  # noqa: E402
from bench.harness.spec import Spec  # noqa: E402
from bench.harness.traffic import Mix  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    spec = Spec(ROOT)
    cell = spec.cell(args.workload)
    chip(cell)
    conf, traffic = cell.config, cell.traffic
    archs = [spec.architecture(conf[s], "arch") for s in model.SIDES]
    rx, tx = model.configs(conf, archs)
    weights = model.make_weights(rx, tx, args.seed,
                                 model.DTYPES[conf["dtype"]],
                                 rules=[a.WEIGHT_RULES for a in archs])
    system = model.build_system(rx, tx, weights)
    for rate in (float(x) for x in args.rates.split(",")):
        eng = model.make_engine(system, rx, conf["engine"])
        serve.warm_up(system, eng, rx.name,
                      Mix(traffic, rx.vocab_size, args.seed, stream=1,
                          rate=rate).planned(args.seconds))
        w = serve.run_window(system, eng, rx.name, traffic,
                             Mix(traffic, rx.vocab_size, args.seed,
                                 rate=rate), args.seconds)
        ttft = measures.ttft_s(w)
        due = w.due_in_window()
        print(json.dumps({
            "rate": rate, "offered_per_s": len(due) / w.seconds,
            "completed_per_s": sum(r.tokens is not None and r.done_t >= w.w0
                                   for r in w.requests) / w.seconds,
            "ttft_p50_ms": measures.percentile(ttft, 50) * 1e3,
            "ttft_p95_ms": measures.percentile(ttft, 95) * 1e3,
            "itl_p95_ms": measures.percentile(measures.itl_s(w), 95) * 1e3,
            "no_first_token": sum(r.ready_t is None for r in due),
            "tokens_per_s": measures.tokens(w) / w.seconds,
            "slots": eng.num_active, "steps": len(w.steps)}), flush=True)
        system.engines.clear()
        del eng
        gc.collect()


if __name__ == "__main__":
    main()
