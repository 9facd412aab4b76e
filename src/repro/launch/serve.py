"""Serving launcher: batched prefill + decode with optional quantization.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --smoke \
        --batch 4 --prompt-len 32 --gen 16 [--quant int8]

    # the paper's integer-only LSTM path (fused [i|f|z|o] executor):
    PYTHONPATH=src python -m repro.launch.serve --arch lstm-rnnt --smoke \
        --quant int8-lstm --backend interpret

    # same engine, integer GRU cell (packed [r|u|n], single h carry):
    PYTHONPATH=src python -m repro.launch.serve --arch gru-rnnt --smoke \
        --quant int8-gru --backend interpret

Continuous-batching engine mode (``--engine``, int8-lstm / int8-gru):
instead of
one fixed static batch, a queue of requests with mixed prompt lengths and
generation budgets is served through ``launch/engine.py`` -- admitted into
``--slots`` decode-batch rows, prefilled by teacher-forcing through the same
jitted fused step that decodes, and evicted mid-flight when their budget is
spent.  ``--chunk K`` enables chunked prefill: up to K prompt tokens per
slot per engine step (one masked ``(S, K)`` dispatch instead of K), cutting
time-to-first-token ~K-fold on prompt-heavy workloads while every stream
stays bit-identical to ``--chunk 1`` and to decoding it alone.
``--speculate k`` enables speculative decoding: each generating slot's
n-gram drafter proposes up to k continuation tokens per step and one masked
``(S, k+1)`` verify dispatch accepts the longest greedy-confirmed prefix
(1..k+1 tokens emitted per slot per step), again bit-identical to
``--speculate 0``.  ``--policy`` picks the slot-scheduling policy (fifo |
priority | srf | rr | fifo-reject) and ``--oversubscribe R`` lets up to
``ceil(R * slots)`` streams be live at once, time-multiplexed through the
host-side integer-state pool -- every stream still bit-identical to
decoding it alone.  The workload is either synthetic (``--requests N``) or
a JSON trace (``--trace requests.json``, entries ``{"prompt_len"|"prompt",
"gen", "id"?}``).  Reported metrics include mean TTFT (steps + wall-clock),
per-stream tokens/sec, and -- under speculation -- the draft accept rate
and mean accepted tokens per verify step.

    PYTHONPATH=src python -m repro.launch.serve --arch lstm-rnnt --smoke \
        --quant int8-lstm --engine --slots 8 --requests 16 --chunk 4 \
        --speculate 4

Fleet mode (``--shards N``, requires ``--engine``): the same workload served
through ``launch/fleet.py``'s admission router over N per-shard engines --
least-loaded routing, capped retry/backoff on transient admission failures,
fifo-reject degradation when saturated, and shard-kill recovery that
migrates or replays every in-flight stream bit-exactly.  ``--fault-spec``
takes a JSON object (inline, or ``@path/to/spec.json``) in the
``FaultInjector.from_spec`` schema:

    PYTHONPATH=src python -m repro.launch.serve --arch lstm-rnnt --smoke \
        --quant int8-lstm --engine --shards 2 --slots 4 --requests 16 \
        --fault-spec '{"kills": [{"shard": 0, "at_frac": 0.5}]}'

Each shard gets its own disjoint device mesh when the host exposes enough
devices (``runtime.sharding.fleet_meshes``; on CPU set
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` BEFORE jax starts),
and shares the default device otherwise.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp

# <repo>/.jax_cache: a fixed path, because the cache key includes it
_DEFAULT_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, os.pardir,
    os.pardir, ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads
    the variable itself, and nothing else is set here).  Otherwise the
    cache lives in the repository's ``.jax_cache``.  Call it before the
    first compile.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE_DIR)
    return _DEFAULT_CACHE_DIR


def _scan_prefill(decode, params, prompt, state):
    """Teacher-force the whole prompt through decode in ONE scanned pass.

    Replaces the former per-token python loop (one dispatch per prompt
    position) with a single jitted ``lax.scan``; returns the last-position
    logits and the warmed decode state.
    """

    # first token primes the (B, V) logits carry; the scan then keeps only
    # the latest logits live instead of stacking a (T, B, V) array
    logits, state = decode(params, prompt[:, :1], state)

    def body(carry, tok):
        state, _ = carry
        logits, state = decode(params, tok[:, None], state)
        return (state, logits), None

    (state, logits), _ = jax.lax.scan(
        body, (state, logits), jnp.swapaxes(prompt[:, 1:], 0, 1))
    return logits, state


def _greedy_loop(decode, params, logits, state, n_gen):
    out_tokens = []
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    for _ in range(n_gen):
        logits, state = decode(params, tok, state)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    return jnp.concatenate(out_tokens, axis=1)


def _quantized_recurrent_lm(args, cfg):
    """Init + calibrate + quantize the stacked recurrent LM once (shared by
    the static path and the engine path)."""
    from repro.models import lstm_lm, model_zoo

    want_cell = args.quant.split("-", 1)[1]  # int8-lstm -> lstm
    if cfg.family != "lstm":
        raise SystemExit(
            f"--quant {args.quant} requires an lstm-family arch (e.g. "
            f"lstm-rnnt, gru-rnnt), got {cfg.name} ({cfg.family})")
    have_cell = lstm_lm.rnn_cell(cfg)
    if have_cell != want_cell:
        raise SystemExit(
            f"--quant {args.quant} expects rnn_cell={want_cell!r} but "
            f"{cfg.name} uses {have_cell!r} (try --quant int8-{have_cell})")
    bundle = model_zoo.build(cfg)
    params, _ = bundle.init(jax.random.PRNGKey(0))
    calib = jax.random.randint(
        jax.random.PRNGKey(2), (args.batch, max(args.prompt_len, 8)), 0,
        cfg.vocab_size)
    t0 = time.time()
    qlayers = lstm_lm.quantize_stack(params, cfg, calib)
    print(f"calibrated+quantized {len(qlayers)} {have_cell.upper()} layers "
          f"in {time.time() - t0:.1f}s (backend={args.backend})")
    return params, qlayers


def _serve_engine(args, cfg) -> None:
    """Continuous-batching serving of the integer recurrent LM."""
    from repro.launch import engine as E

    params, qlayers = _quantized_recurrent_lm(args, cfg)
    if args.trace:
        requests = E.load_trace(args.trace, cfg.vocab_size, seed=1)
    else:
        requests = E.synthetic_trace(
            args.requests, cfg.vocab_size, seed=1,
            prompt_lens=(args.prompt_len // 2 or 1, args.prompt_len),
            gen_lens=(args.gen // 2 or 1, args.gen))
    if not requests:
        raise SystemExit("engine: empty workload (use --requests N >= 1 or "
                         "a non-empty --trace)")
    eng = E.ContinuousBatchingEngine(
        params, qlayers, cfg, n_slots=args.slots, backend=args.backend,
        chunk=args.chunk, speculate=args.speculate, policy=args.policy,
        oversubscribe=args.oversubscribe)
    eng.submit_all(requests)
    results, stats = eng.run()
    print(f"arch={cfg.name} quant={args.quant} engine slots={args.slots} "
          f"chunk={args.chunk} speculate={args.speculate} "
          f"policy={stats.policy} oversubscribe={stats.oversubscribe} "
          f"backend={args.backend}")
    print(f"served {len(results)}/{len(requests)} requests in "
          f"{stats.wall_s:.2f}s ({stats.steps} steps)")
    print(f"decode tokens/s: {stats.tokens_per_s:.1f} "
          f"(+{stats.prompt_tokens} prompt tokens)")
    print(f"slot occupancy: {stats.occupancy:.2f}")
    print(f"mean TTFT: {stats.mean_ttft_steps:.1f} steps / "
          f"{stats.mean_ttft_s * 1e3:.1f} ms; "
          f"mean stream tokens/s: {stats.mean_stream_tokens_per_s:.1f}")
    if stats.preemptions or stats.resumes or stats.rejected \
            or stats.oversubscribe > 1:
        print(f"scheduling: peak live {stats.peak_live} "
              f"(slots={stats.n_slots}), {stats.preemptions} preemptions, "
              f"{stats.resumes} resumes, {stats.rejected} rejected, "
              f"{stats.pool_state_bytes} B/stream parked state")
    if args.speculate:
        print(f"speculation: accept rate {stats.accept_rate:.2f} "
              f"({stats.accepted_draft_tokens}/{stats.drafted_tokens} "
              f"drafts), {stats.accepted_tokens_per_spec_step:.2f} "
              f"tokens/slot-step over {stats.spec_slot_steps} speculating "
              f"slot-steps ({stats.spec_steps} verify steps)")
    first = results[requests[0].rid]
    print("sample:", first.tokens)


def _load_fault_spec(raw):
    """``--fault-spec`` value -> FaultInjector (inline JSON or @file)."""
    import json

    from repro.launch import fleet as F

    if raw is None:
        return None
    if raw.startswith("@"):
        with open(raw[1:]) as f:
            spec = json.load(f)
    else:
        spec = json.loads(raw)
    if not isinstance(spec, dict):
        raise SystemExit(f"--fault-spec: expected a JSON object, "
                         f"got {type(spec).__name__}")
    return F.FaultInjector.from_spec(spec)


def _serve_fleet(args, cfg) -> None:
    """Sharded serving of the integer recurrent LM through the fleet
    router (admission routing + fault-plane recovery)."""
    from repro.launch import engine as E
    from repro.launch import fleet as F
    from repro.runtime import sharding as shlib

    params, qlayers = _quantized_recurrent_lm(args, cfg)
    if args.trace:
        requests = E.load_trace(args.trace, cfg.vocab_size, seed=1)
    else:
        requests = E.synthetic_trace(
            args.requests, cfg.vocab_size, seed=1,
            prompt_lens=(args.prompt_len // 2 or 1, args.prompt_len),
            gen_lens=(args.gen // 2 or 1, args.gen),
            arrival_span=max(args.requests // 2, 1))
    if not requests:
        raise SystemExit("fleet: empty workload (use --requests N >= 1 or "
                         "a non-empty --trace)")
    meshes = shlib.fleet_meshes(args.shards)
    placed = sum(m is not None for m in meshes)
    router = F.FleetRouter(
        params, qlayers, cfg, n_shards=args.shards,
        slots_per_shard=args.slots, backend=args.backend, chunk=args.chunk,
        speculate=args.speculate, policy=args.policy,
        oversubscribe=args.oversubscribe, injector=_load_fault_spec(
            args.fault_spec), meshes=meshes)
    router.warmup()
    router.submit_all(requests)
    results, stats = router.run()
    print(f"arch={cfg.name} quant={args.quant} fleet shards={args.shards} "
          f"slots/shard={args.slots} chunk={args.chunk} "
          f"policy={args.policy} oversubscribe={args.oversubscribe} "
          f"backend={args.backend} meshes={placed}/{args.shards}")
    print(f"served {stats.completed}/{stats.submitted} requests in "
          f"{stats.wall_s:.2f}s ({stats.fleet_steps} fleet steps); "
          f"{stats.rejected} rejected, {stats.lost} lost")
    print(f"goodput: {stats.goodput_tokens_per_step:.2f} tokens/step "
          f"({stats.tokens_per_s:.1f} tokens/s)")
    print(f"fault plane: {stats.kills} kills, {stats.restarts} restarts, "
          f"{stats.hang_events} hung steps, {stats.migrated_streams} "
          f"migrated, {stats.replayed_streams} replayed, "
          f"{stats.rerouted_pending} rerouted, {stats.admit_retries} "
          f"admission retries")
    for i, s in enumerate(stats.shards):
        print(f"  shard {i}: {'alive' if s.alive else 'dead '} "
              f"steps={s.steps} occupancy={s.occupancy(args.slots):.2f} "
              f"tokens={s.generated_tokens} adopted={s.adopted} "
              f"stragglers={s.stragglers} hung={s.hung} "
              f"kills={s.kills} restarts={s.restarts}")
    done = [r for r in results.values() if r.tokens and not r.truncated]
    if done:
        ttfts = sorted(r.ttft_steps for r in done
                       if r.ttft_steps is not None)
        if ttfts:
            print(f"TTFT p50/p99: {ttfts[len(ttfts) // 2]} / "
                  f"{ttfts[min(len(ttfts) - 1, int(len(ttfts) * 0.99))]} "
                  f"fleet steps")
        print("sample:", done[0].tokens)


def _serve_int8_recurrent(args, cfg) -> None:
    """Integer-only serving of the stacked recurrent LM (paper sec 3.2).

    The scanned prefill runs the hoisted two-stage executor: per layer, the
    whole prompt's packed input GEMM is one time-batched int8 matmul and
    only the recurrent stage scans over time (as the persistent Pallas
    sequence kernel under ``--backend pallas|interpret``), so prompt
    tokens/s no longer pays a per-token input matmul dispatch.
    """
    from repro.models import lstm_lm

    params, qlayers = _quantized_recurrent_lm(args, cfg)
    prompt = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0,
        cfg.vocab_size)
    prefill = jax.jit(lambda p, toks, s: lstm_lm.quant_prefill(
        p, qlayers, cfg, toks, s, backend=args.backend))
    decode = jax.jit(lambda p, t, s: lstm_lm.quant_decode_step(
        p, qlayers, cfg, t, s, backend=args.backend))

    state = lstm_lm.init_quant_decode_state(qlayers, args.batch)
    t0 = time.time()
    logits, state = prefill(params, prompt, state)
    jax.block_until_ready(logits)
    prefill_s = time.time() - t0
    t0 = time.time()
    gen = _greedy_loop(decode, params, logits, state, args.gen)
    gen_s = time.time() - t0
    print(f"arch={cfg.name} quant={args.quant} backend={args.backend}")
    print(f"prompt tokens/s: {args.batch * args.prompt_len / prefill_s:.1f}")
    print(f"decode tokens/s: {args.batch * args.gen / gen_s:.1f}")
    print("sample:", gen[0].tolist())


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--quant", default="none",
                    choices=["none", "int8", "int8-lstm", "int8-gru"])
    ap.add_argument("--backend", default="xla",
                    choices=["xla", "pallas", "interpret"],
                    help="integer recurrent kernel backend "
                         "(int8-lstm / int8-gru only)")
    ap.add_argument("--engine", action="store_true",
                    help="continuous-batching engine (int8-lstm / "
                         "int8-gru only)")
    ap.add_argument("--slots", type=int, default=8,
                    help="decode-batch rows of the engine")
    ap.add_argument("--chunk", type=int, default=1,
                    help="prefill chunk size K for --engine: feed up to K "
                         "prompt tokens per slot per step (one masked "
                         "(S, K) dispatch instead of K one-token steps). "
                         "Cuts TTFT ~K-fold on prompt-heavy workloads; "
                         "bit-exact vs --chunk 1. Pure generation is "
                         "unaffected, so K>1 only helps when prompts are "
                         "long relative to generation budgets")
    ap.add_argument("--speculate", type=int, default=0,
                    help="draft budget k for --engine speculative decoding: "
                         "an n-gram drafter proposes up to k continuation "
                         "tokens per generating slot per step, verified in "
                         "one masked (S, k+1) dispatch that emits every "
                         "greedy-confirmed token (1..k+1 per slot per "
                         "step). Bit-exact vs --speculate 0; pays off on "
                         "self-repetitive streams (the drafter only knows "
                         "each stream's own history)")
    ap.add_argument("--policy", default="fifo",
                    help="slot-scheduling policy for --engine (fifo | "
                         "priority | srf | rr | fifo-reject; see "
                         "launch/scheduler.py). fifo reproduces the "
                         "pre-scheduler engine exactly; the others may "
                         "preempt streams to the host-side state pool and "
                         "resume them later, bit-exactly")
    ap.add_argument("--oversubscribe", type=float, default=1.0,
                    help="admission headroom for --engine as a multiple of "
                         "--slots: up to ceil(ratio * slots) streams may be "
                         "live at once, time-multiplexed through the state "
                         "pool by preempting policies. 1.0 (default) never "
                         "holds more streams than slots")
    ap.add_argument("--shards", type=int, default=None,
                    help="serve through the fleet router over N per-shard "
                         "engines (requires --engine; launch/fleet.py). "
                         "Each shard gets --slots decode rows and its own "
                         "device mesh when enough devices exist")
    ap.add_argument("--fault-spec", default=None,
                    help="fault-injection spec for --shards: inline JSON or "
                         "@file, schema per fleet.FaultInjector.from_spec "
                         "(kills / hangs / admission failures, all seeded "
                         "and deterministic)")
    ap.add_argument("--requests", type=int, default=16,
                    help="synthetic workload size for --engine")
    ap.add_argument("--trace", default=None,
                    help="JSON request trace for --engine "
                         "(see launch/engine.py:load_trace)")
    args = ap.parse_args()
    if args.prompt_len < 1:
        # decode needs at least one teacher-forced token to produce logits
        ap.error("--prompt-len must be >= 1")
    if args.chunk < 1:
        ap.error("--chunk must be >= 1")
    if args.speculate < 0:
        ap.error("--speculate must be >= 0")
    if args.oversubscribe < 1.0:
        ap.error("--oversubscribe must be >= 1.0")
    if (args.policy != "fifo" or args.oversubscribe > 1.0) \
            and not args.engine:
        ap.error("--policy/--oversubscribe require --engine (scheduling "
                 "is a continuous-batching concern)")
    if args.speculate and not args.engine:
        ap.error("--speculate requires --engine (speculative decoding is a "
                 "continuous-batching program)")
    if args.engine and args.quant not in ("int8-lstm", "int8-gru"):
        ap.error("--engine requires --quant int8-lstm or int8-gru (the "
                 "integer recurrent LMs are the only models with per-slot "
                 "integer decode state)")
    if args.shards is not None and not args.engine:
        ap.error("--shards requires --engine (the fleet router drives "
                 "continuous-batching engines)")
    if args.shards is not None and args.shards < 1:
        ap.error("--shards must be >= 1")
    if args.fault_spec is not None and args.shards is None:
        ap.error("--fault-spec requires --shards (faults are injected at "
                 "the fleet router)")

    from repro.configs.registry import get_config
    from repro.models import model_zoo, quant_transformer

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.engine:
        if args.shards is not None:
            _serve_fleet(args, cfg)
        else:
            _serve_engine(args, cfg)
        return
    if args.quant in ("int8-lstm", "int8-gru"):
        _serve_int8_recurrent(args, cfg)
        return

    bundle = model_zoo.build(cfg)
    params, _ = bundle.init(jax.random.PRNGKey(0))
    if args.quant == "int8":
        params = quant_transformer.quantize_param_tree(params)
        bundle = quant_transformer.quantize_bundle(bundle)  # for init_state

    constrain = lambda x, logical=None: x
    prompt = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0,
        cfg.vocab_size)

    decode = jax.jit(lambda p, t, s: bundle.decode(p, t, s, constrain))
    state = bundle.init_state(args.batch, args.max_len)
    # prefill by teacher-forcing the prompt through decode (cache warmup)
    t0 = time.time()
    logits, state = _scan_prefill(decode, params, prompt, state)
    jax.block_until_ready(logits)
    prefill_s = time.time() - t0
    t0 = time.time()
    gen = _greedy_loop(decode, params, logits, state, args.gen)
    gen_s = time.time() - t0
    print(f"arch={cfg.name} quant={args.quant}")
    print(f"prompt tokens/s: {args.batch * args.prompt_len / prefill_s:.1f}")
    print(f"decode tokens/s: {args.batch * args.gen / gen_s:.1f}")
    print("sample:", gen[0].tolist())


if __name__ == "__main__":
    main()
