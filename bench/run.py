"""Runs one cell of the benchmark once and prints its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration and a
traffic mix. The run builds the federation from the seed, warms up every
shape the window will use, serves the mix for ``--seconds`` through the
program's own serving path, then checks a sample of what it served against
the plain float32 reference. With ``--trace 0`` it reports the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from a
device trace of the same window. The last line of standard output is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
``breakdown`` when traced, and ``check``: each number compared, with its
limit). It needs a TPU: anywhere else it exits with code 3 and prints no
result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402

# one fixed directory inside the checkout, whatever compiles how fast, so
# that every run after a cell's first reads all its programs back
jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

from bench.harness.cell import NoChip, print_result, run_cell  # noqa: E402
from bench.harness.spec import Spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(Spec(ROOT), args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
