"""Run one cell of the benchmark on the chip it is started on.

    python3 bench/run.py --workload lstm-rnnt.chat --seed 7 --seconds 30 \
        --trace 0

Run from the root of a checkout.  ``BENCHMARK.json`` there names the cell's
configuration and traffic; ``bench/`` holds their files, the metric
readers and the reference.  Without a TPU (or with fewer chips than the
cell asks for) it exits non-zero and prints no result.  The last line of
standard output is the result as one JSON object; the numbers compared for
``correct`` are also the last lines of standard error.
"""
import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, BENCH)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import cell

    cell.enable_cache()
    paths = cell.Paths(spec=os.path.join(ROOT, "BENCHMARK.json"), data=BENCH,
                       metrics=os.path.join(BENCH, "metrics"),
                       trace_dir=os.path.join(ROOT, ".bench_trace"))
    cell.run(paths, args.workload, args.seed, args.seconds,
             bool(args.trace), T_PROC,
             log=lambda m: print(m, flush=True))


if __name__ == "__main__":
    main()
