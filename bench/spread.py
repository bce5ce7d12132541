"""Runs one cell the way a check does and reports how widely its runs
spread: ``--sets`` sets of runs, each run a process of its own
(``bench/run.py``), the same seeds in every set. Appends each run's result
line, with the last lines of its standard error, to ``--out`` (JSON lines),
then prints, for each metric and each set, the distance between the first
and third quartiles over the median (``statistics.quantiles(n=4)``), and
five times the widest of those. A run that fails or prints no result stops
the whole.

    python3 bench/spread.py --workload c2c-closed --seconds 51 \\
        --seeds 11,12,13,14,15,16 --sets 2 --out bench_out/c2c.jsonl
"""
import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def spread(values):
    """Quartile distance over the median (Python's quartiles); None where
    the median is 0, as for a counter that reads 0 in every run."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2 if q2 else None


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--timeout", type=float, default=1300.0)
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    sets = []
    for n in range(args.sets):
        runs = []
        for seed in seeds:
            cmd = [sys.executable, "bench/run.py", "--workload",
                   args.workload, "--seed", str(seed), "--seconds",
                   str(args.seconds), "--trace", str(args.trace)]
            t0 = time.perf_counter()
            p = subprocess.run(cmd, cwd=ROOT, capture_output=True,
                               text=True, timeout=args.timeout)
            wall = time.perf_counter() - t0
            lines = p.stdout.strip().splitlines()
            rec = {"set": n, "seed": seed, "rc": p.returncode, "wall_s": wall,
                   "stderr": p.stderr.strip().splitlines()[-12:]}
            if p.returncode == 0 and lines:
                rec["result"] = json.loads(lines[-1])
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
            if "result" not in rec:
                print(json.dumps(rec), flush=True)
                return 1
            runs.append(rec["result"])
            print(json.dumps({"set": n, "seed": seed, "wall_s": round(wall, 1),
                              "correct": rec["result"]["correct"],
                              **{k: v["value"] for k, v in
                                 rec["result"]["metrics"].items()}}),
                  flush=True)
        sets.append(runs)
    if len(seeds) >= 2:
        names = sorted(set.intersection(*(set(r["metrics"]) for s in sets
                                          for r in s)))
        for m in names:
            spreads = [spread([r["metrics"][m]["value"] for r in s])
                       for s in sets]
            medians = [statistics.median(r["metrics"][m]["value"] for r in s)
                       for s in sets]
            known = [x for x in spreads if x is not None]
            print(json.dumps({"metric": m, "medians": medians,
                              "spreads": spreads,
                              "five_times_widest":
                                  5 * max(known) if known else None}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
