"""Chip smoke test: the integer serving path on a TPU, checked bit for bit.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the fleet path on four chips

One chip:

1. **Golden replay.**  Every LSTM and GRU layer variant, the LM decodes and
   the GRU engine cases in ``tests/golden/*.json`` run on the chip under
   ``pallas`` and under ``xla``; both must give the checked-in integers.
   The cases (float weights, calibration, quantization) are built on the
   host CPU, where the goldens were made; the integer execution runs on
   the chip.
2. **Full-width serve.**  ``lstm-rnnt`` and then ``gru-rnnt`` from
   ``CONFIGS`` (10 x 2048, random weights from a seed), quantized by
   ``serve``'s own code, are served through
   ``ContinuousBatchingEngine(backend="pallas")``: 8 slots, chunked prefill
   (K=8, the masked kernel), ``srf`` at oversubscription 2 (pool swaps).
   Every stream must equal ``decode_single(..., backend="xla")``.

``--four-chips`` runs only the fleet path: ``lstm-rnnt`` at full width
behind ``FleetRouter`` over 4 shards, one chip each, with shard 0
hard-killed at half progress and restarted; every stream must equal
``decode_single(..., backend="pallas")``.

Without a TPU the script exits non-zero before any phase.  The last line
of its output is ``{"ok": true, "device": {...}}``, printed only when every
phase passed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402

from repro.launch import serve  # noqa: E402

SLOTS = 8
CHUNK = 8
PROMPT_LENS = (16, 64)
GEN_LENS = (8, 16, 32)


class SmokeFailure(Exception):
    """A phase's output disagreed with its reference."""


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(n_chips: int):
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform is {platform!r}); "
                 f"this script runs only on a TPU")
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: needs {n_chips} TPU chips, found "
                 f"{len(devices)}")
    return devices


# ---------------------------------------------------------------------------
# Phase 1: golden replay
# ---------------------------------------------------------------------------


def _layer_on(device, case):
    xs_q, arrays, spec = case
    return jax.device_put((xs_q, arrays), device) + (spec,)


def _lm_on(device, built):
    params, qlayers, cfg, prompt = built
    return (jax.device_put(params, device),
            [(jax.device_put(a, device), spec) for a, spec in qlayers],
            cfg, prompt)


def golden_replay(chip, host) -> int:
    """Replay every golden case on ``chip`` under ``pallas`` and ``xla``;
    returns the number of (case, backend) pairs checked.  Raises
    ``SmokeFailure`` on any drift."""
    from repro.models import gru as GR
    from repro.models import lstm as L
    from repro.testing import golden

    gdir = os.path.join(ROOT, "tests", "golden")
    want_lstm = golden.load_goldens(os.path.join(gdir, "lstm_goldens.json"))
    want_gru = golden.load_goldens(os.path.join(gdir, "gru_goldens.json"))
    with jax.default_device(host):
        lms = {arch: _lm_on(chip, golden.build_lm_case(arch))
               for arch in ("lstm-rnnt", "gru-rnnt")}

    # (label, build on host, run on chip for a backend, golden entry)
    cases = []
    for v in L.ALL_VARIANTS:
        cases.append((f"lstm/{v.name}",
                      lambda v=v: _layer_on(chip, golden.build_variant_case(v)),
                      golden.execute_case,
                      want_lstm["variants"][golden.variant_key(v)]))
    for v in GR.ALL_VARIANTS:
        cases.append((f"gru/{v.name}",
                      lambda v=v: _layer_on(
                          chip, golden.build_gru_variant_case(v)),
                      golden.execute_case,
                      want_gru["variants"][golden.gru_variant_key(v)]))
    for arch, want in (("lstm-rnnt", want_lstm), ("gru-rnnt", want_gru)):
        cases.append((f"{arch}/lm-decode",
                      lambda arch=arch: lms[arch],
                      lambda built, b, arch=arch: golden.run_lm_case(
                          b, arch, built=built),
                      want["lm"]))
    for policy, ratio in golden.ENGINE_GOLDEN_CASES:
        cases.append((f"gru-rnnt/engine-{policy}-{ratio}",
                      lambda: lms["gru-rnnt"],
                      lambda built, b, p=policy, r=ratio:
                          golden.run_engine_case("gru-rnnt", p, r, b,
                                                 built=built),
                      want_gru["engine"][f"{policy}-{ratio}"]))

    backends = ("pallas", "xla")
    drifted = []
    checked = 0
    for label, build, run, want in cases:
        with jax.default_device(host):
            built = build()
        for backend in backends:
            got = run(built, backend)
            if any(got[k] != want[k] for k in want):
                drifted.append(f"{label}[{backend}]")
            checked += 1
    log(f"golden replay: {checked - len(drifted)}/{checked} cases equal the "
        f"checked-in integers ({len(cases)} cases x {'/'.join(backends)})")
    if drifted:
        raise SmokeFailure(f"golden cases drifted: {drifted}")
    return checked


# ---------------------------------------------------------------------------
# Phase 2: full-width serve through the engine
# ---------------------------------------------------------------------------


def build_model(cfg, seed_batch: int = 4):
    """Seeded init + calibration + quantization, by ``serve``'s own code."""
    from repro.models import lstm_lm

    args = argparse.Namespace(
        quant=f"int8-{lstm_lm.rnn_cell(cfg)}", batch=seed_batch,
        prompt_len=max(PROMPT_LENS), backend="pallas")
    return serve._quantized_recurrent_lm(args, cfg)


def workload(cfg, n_requests: int, seed: int):
    """Mixed lengths arriving over the first ``n_requests // 2`` engine
    steps: short late arrivals make ``srf`` park long residents in the
    pool."""
    from repro.launch import engine as E

    return E.synthetic_trace(n_requests, cfg.vocab_size, seed=seed,
                             prompt_lens=PROMPT_LENS, gen_lens=GEN_LENS,
                             arrival_span=n_requests // 2)


def check_streams(label, params, qlayers, cfg, requests, results, backend):
    """Compare every served stream with ``decode_single`` on ``backend``;
    returns the number of bit-exact streams."""
    from repro.launch import engine as E

    t0 = time.perf_counter()
    bad = []
    for r in requests:
        ref = E.decode_single(params, qlayers, cfg, r.prompt,
                              r.max_new_tokens, backend=backend)
        got = results[r.rid].tokens
        if got != ref:
            first = next((i for i, (a, b) in enumerate(zip(got, ref))
                          if a != b), min(len(got), len(ref)))
            bad.append(f"{r.rid}@{first}")
    exact = len(requests) - len(bad)
    ref_s = time.perf_counter() - t0
    log(f"{label}: {exact}/{len(requests)} streams bit-exact vs "
        f"decode_single({backend}) (reference {ref_s:.1f}s incl. compile)")
    if bad:
        raise SmokeFailure(f"{label}: streams differ from decode_single "
                           f"(rid@first differing token): {bad}")
    return exact


def serve_full_width(arch: str, n_requests: int) -> int:
    """Serve ``arch`` through the engine and check every stream; returns
    the number of bit-exact streams."""
    from repro.configs.registry import CONFIGS
    from repro.launch import engine as E

    cfg = CONFIGS[arch]
    t0 = time.perf_counter()
    params, qlayers = build_model(cfg)
    jax.block_until_ready(qlayers)
    log(f"{arch}: {cfg.n_layers} x {cfg.d_rnn} layers, vocab "
        f"{cfg.vocab_size}, quantized in {time.perf_counter() - t0:.1f}s")

    def engine():
        return E.ContinuousBatchingEngine(
            params, qlayers, cfg, n_slots=SLOTS, backend="pallas",
            chunk=CHUNK, policy="srf", oversubscribe=2.0)

    # warm-up: one throwaway engine compiles the chunk-advance, chunk-step,
    # one-token, reset and resume programs the served engine then reuses
    warm = engine()
    warm.submit_all(workload(cfg, SLOTS + 2, seed=99))
    t0 = time.perf_counter()
    warm.run()
    compile_s = time.perf_counter() - t0

    requests = workload(cfg, n_requests, seed=1)
    eng = engine()
    eng.submit_all(requests)
    t0 = time.perf_counter()
    results, stats = eng.run()
    serve_s = time.perf_counter() - t0
    log(f"{arch}: warm-up (compile) {compile_s:.1f}s; served "
        f"{len(results)}/{len(requests)} requests in {serve_s:.2f}s, "
        f"{stats.steps} steps, {stats.generated_tokens} tokens generated + "
        f"{stats.prompt_tokens} prompt tokens; {stats.preemptions} "
        f"preemptions, {stats.resumes} resumes (backend=pallas, "
        f"slots={SLOTS}, chunk={CHUNK}, policy=srf, oversubscribe=2.0)")
    if stats.resumes == 0:
        raise SmokeFailure(f"{arch}: no stream was parked and resumed; the "
                           f"pool path did not run")
    for r in requests:
        res = results[r.rid]
        if res.truncated or len(res.tokens) != r.max_new_tokens:
            raise SmokeFailure(f"{arch}: request {r.rid} was cut short")
    return check_streams(arch, params, qlayers, cfg, requests, results,
                         backend="xla")


# ---------------------------------------------------------------------------
# --four-chips: the fleet path, one shard per chip
# ---------------------------------------------------------------------------


def serve_fleet(n_shards: int, n_requests: int) -> int:
    """``lstm-rnnt`` through ``FleetRouter`` over one-chip shards, shard 0
    hard-killed at half progress and restarted; returns the number of
    bit-exact streams."""
    from repro.configs.registry import CONFIGS
    from repro.launch import engine as E
    from repro.launch import fleet as F
    from repro.runtime import sharding as shlib

    cfg = CONFIGS["lstm-rnnt"]
    t0 = time.perf_counter()
    params, qlayers = build_model(cfg)
    jax.block_until_ready(qlayers)
    log(f"{cfg.name}: {cfg.n_layers} x {cfg.d_rnn} layers, quantized in "
        f"{time.perf_counter() - t0:.1f}s")
    meshes = shlib.fleet_meshes(n_shards)
    if any(m is None for m in meshes):
        raise SmokeFailure(f"fleet: fewer devices than {n_shards} shards")
    requests = E.synthetic_trace(
        n_requests, cfg.vocab_size, seed=1, prompt_lens=PROMPT_LENS,
        gen_lens=GEN_LENS, arrival_span=n_requests // 2)
    injector = F.FaultInjector.from_spec({"kills": [
        {"shard": 0, "at_frac": 0.5, "restart_after": 4}]})
    router = F.FleetRouter(
        params, qlayers, cfg, n_shards=n_shards, slots_per_shard=SLOTS,
        backend="pallas", injector=injector, meshes=meshes)

    def check_placement(when):
        for i, sh in enumerate(router.shards):
            mesh = sorted(d.id for d in meshes[i].devices.flat)
            on = sorted({d.id for leaf in jax.tree_util.tree_leaves(
                sh.engine.weights) for d in leaf.devices()})
            log(f"fleet shard {i} ({when}): mesh devices {mesh}, weights "
                f"on {on}")
            if on != mesh:
                raise SmokeFailure(f"fleet shard {i}: weights on {on}, not "
                                   f"on its mesh {mesh}")

    check_placement("start")
    t0 = time.perf_counter()
    router.warmup()
    compile_s = time.perf_counter() - t0
    router.submit_all(requests)
    t0 = time.perf_counter()
    results, stats = router.run()
    serve_s = time.perf_counter() - t0
    log(f"fleet: warm-up (compile) {compile_s:.1f}s; served "
        f"{stats.completed}/{stats.submitted} requests in {serve_s:.2f}s "
        f"({stats.fleet_steps} fleet steps, {stats.generated_tokens} "
        f"tokens); {stats.kills} kills, {stats.restarts} restarts, "
        f"{stats.migrated_streams} migrated, {stats.replayed_streams} "
        f"replayed, {stats.rerouted_pending} rerouted, {stats.lost} lost")
    for i, s in enumerate(stats.shards):
        log(f"  shard {i}: {'alive' if s.alive else 'dead'} steps={s.steps} "
            f"tokens={s.generated_tokens} kills={s.kills} "
            f"restarts={s.restarts}")
    check_placement("end")
    if stats.kills != 1 or stats.restarts != 1:
        raise SmokeFailure(f"fleet: expected 1 kill and 1 restart, got "
                           f"{stats.kills} and {stats.restarts}")
    if stats.completed != len(requests) or stats.lost or stats.rejected:
        raise SmokeFailure(f"fleet: {stats.completed}/{len(requests)} "
                           f"completed, {stats.lost} lost, {stats.rejected} "
                           f"rejected")
    # the one-chip run ties the Pallas path to ``xla`` and to the goldens;
    # here the reference only has to decode each stream alone, and the
    # Pallas programs compile in a third of the ``xla`` ones' time
    return check_streams("fleet", params, qlayers, cfg, requests, results,
                         backend="pallas")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-shard fleet path, one chip each")
    args = ap.parse_args()
    cache = serve.enable_compile_cache()
    n_chips = 4 if args.four_chips else 1
    devices = require_tpu(n_chips)
    dev = devices[0]
    log(f"device: {dev.platform} {dev.device_kind} x {len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache}")
    t0 = time.perf_counter()
    if args.four_chips:
        serve_fleet(n_shards=4, n_requests=24)
    else:
        golden_replay(dev, jax.devices("cpu")[0])
        serve_full_width("lstm-rnnt", n_requests=16)
        serve_full_width("gru-rnnt", n_requests=10)
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    try:
        main()
    except SmokeFailure as e:
        sys.exit(f"chip_smoke: FAILED: {e}")
