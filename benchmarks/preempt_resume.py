"""Preemption/resume cost + oversubscribed-scheduling goodput benchmark.

Two measurements, both cashing in the paper's tiny-integer-state property:

* **Swap microbenchmark** -- the wall cost of parking one stream's
  quantized ``(h, c, len)`` state into the host-side pool
  (``slice_state`` + device_get + page write) and restoring it
  (page read + jitted slot write), against the cost of one fused engine
  decode step.  An integer LSTM stream is a few KB, so a full
  preempt+resume round trip should cost on the order of a single step --
  THE reason aggressive scheduling policies are affordable at all (a
  transformer's per-stream KV cache is MBs and grows with context).

* **Bursty goodput** -- the same bursty open-loop trace (bursts of
  ``burst_size`` requests arriving every ``period`` engine steps) served
  two ways:

    - ``fifo-reject`` at ``oversubscribe=1``: an arrival that finds no
      free slot is refused outright -- the classic admission-control
      baseline.  Rejected work is gone; between bursts the surviving
      streams drain and slots sit idle.
    - a preempting policy (default ``srf``) with ``oversubscribe > 1``:
      every arrival is admitted, overflow parks in the state pool, and the
      backlog keeps slots full between bursts.

  A partially-occupied step costs the same fused dispatch as a full one,
  so sustained tokens/s tracks occupancy: the oversubscribed engine must
  win.  Both legs' outputs stay bit-identical per stream to decoding it
  alone (asserted here on every served stream, hard exit on drift).

    PYTHONPATH=src python benchmarks/preempt_resume.py --slots 4
    # CI smoke gate:
    PYTHONPATH=src python benchmarks/preempt_resume.py --slots 4 \
        --bursts 3 --check-speedup 1.05 --out BENCH_preempt.json
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "src")

from repro.launch import engine as E  # noqa: E402
from repro.launch.state_pool import StatePool  # noqa: E402
from repro.models import lstm_lm  # noqa: E402

from engine_throughput import build_quantized_lm  # noqa: E402


def bursty_trace(cfg, *, bursts, burst_size, period, seed):
    """``bursts`` waves of ``burst_size`` requests, one wave every
    ``period`` engine steps -- short prompts, heavy-tailed generation
    budgets (mostly short streams plus the occasional very long one, the
    mix where admission control hurts most: a long survivor pins a slot
    through several burst periods while every arrival it displaced was
    already refused, so the reject leg pays full fused-dispatch steps at
    1/slots occupancy)."""
    rng = np.random.default_rng(seed)
    out = []
    rid = 0
    for b in range(bursts):
        for _ in range(burst_size):
            p = int(rng.choice((2, 3, 4)))
            g = int(rng.choice((4, 6, 8, 40)))
            out.append(E.Request(
                rid=rid, prompt=rng.integers(0, cfg.vocab_size, size=(p,)),
                max_new_tokens=g, arrival=float(b * period)))
            rid += 1
    return out


def swap_microbench(params, qlayers, cfg, slots, backend, reps=50):
    """Mean wall cost of preempt (slice+host copy+pool write), resume
    (pool read+jitted slot write), and one fused decode step."""
    state = lstm_lm.init_quant_decode_state(qlayers, slots,
                                            per_slot_len=True)
    step, _, _, _, _, write = E._engine_step_fns(qlayers, cfg, backend)
    weights = E.serving_weights(params, qlayers)
    pool = StatePool()
    toks = jnp.zeros((slots,), jnp.int32)
    active = jnp.ones((slots,), bool)
    # warm every program (compile outside the timed region)
    _, state = step(weights, toks, state, active)
    pool.put(-1, jax.device_get(lstm_lm.slice_state(state, 0)))
    state = write(state, jnp.int32(0), pool.take(-1))
    jax.block_until_ready(state["h"][0])

    t0 = time.perf_counter()
    for i in range(reps):
        pool.put(i, jax.device_get(lstm_lm.slice_state(state, i % slots)))
    preempt_us = (time.perf_counter() - t0) / reps * 1e6
    t0 = time.perf_counter()
    for i in range(reps):
        state = write(state, jnp.int32(i % slots), pool.take(i))
    jax.block_until_ready(state["h"][0])
    resume_us = (time.perf_counter() - t0) / reps * 1e6
    t0 = time.perf_counter()
    for _ in range(reps):
        _, state = step(weights, toks, state, active)
    jax.block_until_ready(state["h"][0])
    step_us = (time.perf_counter() - t0) / reps * 1e6
    return {
        "preempt_us": round(preempt_us, 1),
        "resume_us": round(resume_us, 1),
        "step_us": round(step_us, 1),
        "roundtrip_over_step": round((preempt_us + resume_us) /
                                     max(step_us, 1e-9), 3),
        "state_bytes_per_stream": pool.state_bytes_per_stream,
    }


def run_leg(params, qlayers, cfg, requests, *, slots, backend, policy,
            oversubscribe):
    eng = E.ContinuousBatchingEngine(
        params, qlayers, cfg, n_slots=slots, backend=backend,
        policy=policy, oversubscribe=oversubscribe)
    eng.submit_all([E.Request(rid=r.rid, prompt=r.prompt,
                              max_new_tokens=r.max_new_tokens,
                              priority=r.priority, arrival=r.arrival)
                    for r in requests])
    return eng.run()


def leg_summary(results, stats):
    served = [r for r in results.values() if not r.rejected]
    return {
        "policy": stats.policy,
        "oversubscribe": stats.oversubscribe,
        "tok_s": round(stats.tokens_per_s, 1),
        "generated_tokens": stats.generated_tokens,
        "steps": stats.steps,
        "occupancy": round(stats.occupancy, 3),
        "served": len(served),
        "rejected": stats.rejected,
        "preemptions": stats.preemptions,
        "resumes": stats.resumes,
        "peak_live": stats.peak_live,
        "mean_ttft_steps": round(stats.mean_ttft_steps, 2),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--bursts", type=int, default=4)
    ap.add_argument("--burst-size", type=int, default=None,
                    help="requests per burst (default 3 * slots)")
    ap.add_argument("--period", type=int, default=24,
                    help="engine steps between bursts")
    ap.add_argument("--policy", default="srf",
                    help="preempting policy for the oversubscribed leg")
    ap.add_argument("--oversubscribe", type=float, default=3.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default="xla",
                    choices=["xla", "pallas", "interpret"])
    ap.add_argument("--out", default=None,
                    help="write the JSON artifact here (BENCH_preempt.json)")
    ap.add_argument("--check-speedup", type=float, default=None,
                    help="exit nonzero unless oversubscribed tokens/s / "
                         "reject-baseline tokens/s >= this")
    args = ap.parse_args()
    burst_size = args.burst_size or 3 * args.slots

    params, qlayers, cfg = build_quantized_lm(args.backend)
    requests = bursty_trace(cfg, bursts=args.bursts, burst_size=burst_size,
                            period=args.period, seed=args.seed)

    # warm the compiled programs on a throwaway workload (both legs share
    # them: same slot count, chunk=1) and batch-1 reference shapes
    for p in (2, 3, 4):
        E.decode_single(params, qlayers, cfg, np.zeros((p,), np.int32), 2,
                        backend=args.backend)
    warm = [E.Request(rid=-1 - i, prompt=np.zeros((2,), np.int32),
                      max_new_tokens=2) for i in range(args.slots + 1)]
    run_leg(params, qlayers, cfg, warm, slots=args.slots,
            backend=args.backend, policy=args.policy,
            oversubscribe=args.oversubscribe)

    swap = swap_microbench(params, qlayers, cfg, args.slots, args.backend)

    rej_results, rej_stats = run_leg(
        params, qlayers, cfg, requests, slots=args.slots,
        backend=args.backend, policy="fifo-reject", oversubscribe=1.0)
    ovs_results, ovs_stats = run_leg(
        params, qlayers, cfg, requests, slots=args.slots,
        backend=args.backend, policy=args.policy,
        oversubscribe=args.oversubscribe)

    # bit-exactness: every served stream identical to decoding it alone
    # (verdict computed here, enforced after the artifact is written so a
    # drifting run still leaves numbers to debug with)
    drifted = []
    for r in requests:
        ref = E.decode_single(params, qlayers, cfg, r.prompt,
                              r.max_new_tokens, backend=args.backend)
        if ovs_results[r.rid].tokens != ref:
            drifted.append(("oversub", r.rid))
        if not rej_results[r.rid].rejected and \
                rej_results[r.rid].tokens != ref:
            drifted.append(("reject", r.rid))

    rej = leg_summary(rej_results, rej_stats)
    ovs = leg_summary(ovs_results, ovs_stats)
    speedup = ovs["tok_s"] / rej["tok_s"] if rej["tok_s"] else float("inf")
    served_gain = ovs["served"] / max(rej["served"], 1)

    print(f"preempt_resume,arch={cfg.name},backend={args.backend},"
          f"slots={args.slots},bursts={args.bursts},"
          f"burst_size={burst_size},period={args.period}")
    print(f"preempt_resume/swap,preempt_us={swap['preempt_us']},"
          f"resume_us={swap['resume_us']},step_us={swap['step_us']},"
          f"roundtrip_over_step={swap['roundtrip_over_step']},"
          f"state_bytes={swap['state_bytes_per_stream']}")
    for name, leg in (("reject", rej), ("oversub", ovs)):
        print(f"preempt_resume/{name},policy={leg['policy']},"
              f"tok_s={leg['tok_s']},occupancy={leg['occupancy']},"
              f"served={leg['served']},rejected={leg['rejected']},"
              f"preemptions={leg['preemptions']},resumes={leg['resumes']},"
              f"peak_live={leg['peak_live']}")
    print(f"preempt_resume/speedup,{speedup:.2f},"
          f"served_gain={served_gain:.2f}")

    if args.out:
        artifact = {
            "bench": "preempt_resume",
            "arch": cfg.name,
            "backend": args.backend,
            "slots": args.slots,
            "bursts": args.bursts,
            "burst_size": burst_size,
            "period": args.period,
            "requests": len(requests),
            "swap": swap,
            "reject": rej,
            "oversub": ovs,
            "speedup": round(speedup, 3),
            "served_gain": round(served_gain, 3),
            "bitexact": not drifted,
        }
        with open(args.out, "w") as f:
            json.dump(artifact, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.out}")

    # a hard exit, not an assert, so `python -O` can't skip it
    if drifted:
        leg, rid = drifted[0]
        raise SystemExit(f"FAIL: {leg} leg drifted from decode_single on "
                         f"stream {rid} ({len(drifted)} drifting streams)")
    if args.check_speedup is not None and speedup < args.check_speedup:
        print(f"FAIL: oversubscribed/reject tokens/s {speedup:.2f} < "
              f"required {args.check_speedup:.2f}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
