"""Find a cell's knee once (not part of a benchmark run).

    python3 bench/sweep.py --workload lstm-rnnt.chat --seconds 20 \
        --rates 4,8,12,16 --seeds 5,6

One process builds the cell's model once, then serves one open-loop window
per offered rate and seed (the traffic file's mix with its ``rate_per_s``
replaced) and prints, per window: p95 time to first token, p95 gap between
tokens, tokens/s, how many requests were still queued or live at the
window's close, and how long the drain took.  The knee is the highest rate
at which both p95s stay within the traffic file's ``slo`` and the backlog
does not grow.
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", default="1")
    args = ap.parse_args(argv)

    from harness import cell, loop, metrics

    cell.enable_cache()
    w, conf, mix, _ = cell.load_cell(
        cell.load_json(os.path.join(ROOT, "BENCHMARK.json")), BENCH,
        args.workload)
    cell.require_chips(w["chips"])
    _, engine = cell.build(conf, mix, "pallas",
                           lambda m: print(m, flush=True))
    for rate, seed in ((float(r), int(s)) for r in args.rates.split(",")
                       for s in args.seeds.split(",")):
        mix_r = dict(mix, arrivals=dict(mix["arrivals"], rate_per_s=rate))
        arrivals = cell.schedule(mix_r, args.seconds, seed, conf)
        t0 = loop.clock()
        feed = cell.serve_window(engine, arrivals, t0, args.seconds)
        close = t0 + args.seconds
        ctx = metrics.Context(conf=conf, traffic=mix_r, seconds=args.seconds,
                              t0=t0, reqs=feed.reqs, steps=feed.steps,
                              setup_s=0.0)
        row = {"rate": rate, "seed": seed, "requests": len(arrivals)}
        for name in ("ttft_p95_ms", "itl_p95_ms", "tokens_per_s",
                     "queue_wait_p95_ms", "decode_step_ms"):
            row[name] = metrics.reader(os.path.join(BENCH, "metrics"),
                                       name)(ctx)
        row["backlog_at_close"] = sum(
            1 for r in feed.reqs if not r.stamps or r.stamps[-1] > close)
        row["drain_s"] = max([close] + [r.stamps[-1] for r in feed.reqs
                                        if r.stamps]) - close
        row["unfinished"] = sum(r.tokens is None for r in feed.reqs)
        print("sweep: " + json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
