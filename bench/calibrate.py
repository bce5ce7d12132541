"""Readings that set the limit of a cell's check: for each seed, one run of
the cell (set-up, a window of ``--seconds``, the sample of finished
requests) compared with the reference, and the control judged in the served
path's place: the same reference computed with every matrix-product operand
rounded to fp8 e4m3's precision, whose first choice at each position is read
in the float32 reference. Prints one JSON line per seed: the control's
verdict (``correct``, which has to come out false), the served path's
(``served_correct``) and both worst gaps. All seeds run in one process, so
programs compile once.

    python3 bench/calibrate.py --workload chat-mix --seconds 51 --seeds 1,2,3
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
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

from bench.harness.cell import run_cell  # noqa: E402
from bench.harness.spec import Spec  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rate", type=float, default=None,
                    help="open loop: offer this rate instead of the mix's")
    args = ap.parse_args()
    spec = Spec(ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run_cell(spec, args.workload, seed, args.seconds, False,
                       t_start=time.perf_counter(), control=True,
                       rate=args.rate)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "served_correct": res["served_correct"],
                          **{k: v["value"] for k, v in res["check"].items()},
                          "metrics": {k: v["value"] for k, v in
                                      res["metrics"].items()}}), flush=True)


if __name__ == "__main__":
    main()
