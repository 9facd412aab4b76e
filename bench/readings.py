"""Readings that a cell's correctness limit is set from (not part of a
benchmark run).

    python3 bench/readings.py --workload lstm-rnnt.chat --seconds 10 \
        --seeds 11,12,13 [--control-seeds 11,12,13]

In one process the cell's model is built once; for each seed the engine
serves that seed's traffic for a window at the cell's own load, as a
benchmark run does; then
the widest logit gap of a sample of the served tokens against the float
reference is read (the program's reading), and, for the control seeds, the
widest gap of the tokens that the reference computed one precision lower
(int4) ranks first at the same positions (the control's reading).  One
line per seed; the compiled programs are shared across seeds.
"""
import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, BENCH)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--witness-seeds", default="")
    args = ap.parse_args(argv)

    from harness import cell, check, loop

    cell.enable_cache()
    w, conf, mix, _ = cell.load_cell(
        cell.load_json(os.path.join(ROOT, "BENCHMARK.json")), BENCH,
        args.workload)
    cell.require_chips(w["chips"])
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    witnesses = {int(s) for s in args.witness_seeds.split(",") if s}
    n, length = mix["check"]["requests"], mix["check"]["length"]
    ref = check.Reference(conf)
    control = check.Reference(conf, quant=4)
    witness = check.Reference(conf, quant=8)
    params, engine = cell.build(conf, mix, "pallas",
                                lambda m: print(m, flush=True))
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        arrivals = cell.schedule(mix, args.seconds, seed, conf)
        feed = cell.serve_window(engine, arrivals, loop.clock(),
                                   args.seconds)
        samples = check.sample(feed.reqs, seed, n)
        gaps, differ = check.served_gap(ref, params, samples, n, length)
        row = {"seed": seed, "program": gaps, "differ": differ,
               "unfinished": sum(r.tokens is None for r in feed.reqs)}
        if seed in controls:
            row["control"] = check.control_gap(ref, control, params,
                                               samples, n, length)
        if seed in witnesses:
            row["int8_reference"] = check.control_gap(ref, witness, params,
                                                      samples, n, length)
        row["s"] = round(time.perf_counter() - t, 1)
        print("reading: " + json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
