"""Continuous-batching engine: the core invariant is BIT-exactness.

Integer decode is deterministic and every decode-batch row is computed
independently, so a stream served inside a busy engine batch must produce
exactly the tokens it produces when decoded alone -- regardless of slot
index, co-tenants, slot count, or admission order.  These tests assert that
invariant deterministically (>= 8 concurrent mixed-length streams, the PR
acceptance gate) and -- when hypothesis is installed -- over randomized
workloads and admission orders.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import SMOKE_CONFIGS
from repro.launch import engine as E
from repro.models import lstm_lm, model_zoo

pytestmark = pytest.mark.fast


@pytest.fixture(scope="module")
def qlm():
    """Quantized smoke LSTM LM shared by every test in this module (the
    engine/reference jit caches key on the layer specs)."""
    cfg = SMOKE_CONFIGS["lstm-rnnt"]
    bundle = model_zoo.build(cfg)
    params, _ = bundle.init(jax.random.PRNGKey(0))
    calib = jax.random.randint(jax.random.PRNGKey(2), (4, 8), 0,
                               cfg.vocab_size)
    qlayers = lstm_lm.quantize_stack(params, cfg, calib)
    return params, qlayers, cfg


@pytest.fixture(scope="module")
def qfwd(qlm):
    """One jitted quant_forward shared by the state-helper tests (jax.jit
    retraces per input shape, so a single callable covers them all)."""
    params, qlayers, cfg = qlm
    return jax.jit(lambda p, t, s: lstm_lm.quant_forward(
        p, qlayers, cfg, t, s))


def _reference(params, qlayers, cfg, requests):
    return {r.rid: E.decode_single(params, qlayers, cfg, r.prompt,
                                   r.max_new_tokens) for r in requests}


def test_engine_8_concurrent_streams_bitexact(qlm):
    """Acceptance gate: >= 8 concurrent streams with mixed prompt/gen
    lengths, every stream bit-identical to decoding it alone."""
    params, qlayers, cfg = qlm
    rng = np.random.default_rng(7)
    # mixed lengths drawn from a small set so the batch-1 reference only
    # compiles a handful of distinct prefill shapes
    requests = [
        E.Request(rid=i,
                  prompt=rng.integers(0, cfg.vocab_size, size=(p,)),
                  max_new_tokens=g)
        for i, (p, g) in enumerate(
            [(2, 9), (3, 7), (5, 5), (2, 8), (3, 6), (5, 4),
             (2, 2), (3, 1), (5, 3), (2, 5)])
    ]
    eng = E.ContinuousBatchingEngine(params, qlayers, cfg, n_slots=8)
    eng.submit_all(requests)
    results, stats = eng.run()

    assert stats.max_active >= 8, "workload never filled all 8 slots"
    assert len(results) == len(requests)
    ref = _reference(params, qlayers, cfg, requests)
    for r in requests:
        assert results[r.rid].tokens == ref[r.rid], f"stream {r.rid} drifted"
        assert len(results[r.rid].tokens) == r.max_new_tokens


def test_step_programs_take_weights_as_arguments(qlm):
    """The engine's step program reads the quantized weights as jit
    arguments, not as constants baked into the executable (which would
    copy them into every program and pin them to one device)."""
    params, qlayers, cfg = qlm
    step = E._engine_step_fns(qlayers, cfg, "xla")[0]
    weights = E.serving_weights(params, qlayers)
    state = lstm_lm.init_quant_decode_state(qlayers, 2, per_slot_len=True)
    compiled = step.lower(weights, jnp.zeros((2,), jnp.int32), state,
                          jnp.ones((2,), bool)).compile()
    qbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(weights[1]))
    assert qbytes > 0
    assert compiled.memory_analysis().argument_size_in_bytes >= qbytes
    assert "lstm" not in weights[0]  # the float stack stays behind


def test_lm_head_rows_do_not_depend_on_batch():
    """A row's logits are the same whether the head sees it alone, in an
    engine-sized batch, or among a prompt's positions -- at the published
    head width, where a plain float matmul's rounding does depend on the
    row count."""
    rng = np.random.default_rng(0)
    params = {"lm_head": jnp.asarray(rng.normal(size=(640, 4096)) * 0.02,
                                     jnp.bfloat16)}
    x = jnp.asarray(rng.normal(size=(64, 640)), jnp.float32)
    head = jax.jit(lambda x: lstm_lm._quant_head(params, x))
    batch = np.asarray(head(x[:8]))
    alone = np.concatenate([np.asarray(head(x[i:i + 1])) for i in range(8)])
    np.testing.assert_array_equal(alone, batch)
    np.testing.assert_array_equal(np.asarray(head(x))[:8], batch)
    np.testing.assert_array_equal(
        np.asarray(head(x.reshape(8, 8, 640))).reshape(64, -1)[:8], batch)


def test_admission_order_irrelevant(qlm):
    """The same workload FIFO and shuffled must emit identical per-stream
    tokens (continuous batching is invisible to each stream; slot-count
    invariance is covered by the 8-slot-vs-single-stream tests)."""
    params, qlayers, cfg = qlm
    requests = E.synthetic_trace(6, cfg.vocab_size, seed=11,
                                 prompt_lens=(2, 4, 5), gen_lens=(3, 6))
    outcomes = []
    for order in (list(range(6)), [4, 2, 0, 5, 1, 3]):
        eng = E.ContinuousBatchingEngine(params, qlayers, cfg, n_slots=3)
        eng.submit_all([requests[i] for i in order])
        results, _ = eng.run()
        outcomes.append({rid: res.tokens for rid, res in results.items()})
    assert outcomes[0] == outcomes[1]


def test_eviction_reuses_slots_midflight(qlm):
    """More requests than slots: finished streams must be evicted and their
    slots re-admit pending requests (total steps well under sequential)."""
    params, qlayers, cfg = qlm
    requests = E.synthetic_trace(9, cfg.vocab_size, seed=3,
                                 prompt_lens=(2, 3), gen_lens=(2, 4))
    eng = E.ContinuousBatchingEngine(params, qlayers, cfg, n_slots=3)
    eng.submit_all(requests)
    results, stats = eng.run()
    assert len(results) == 9
    sequential_steps = sum(r.prompt.size + r.max_new_tokens - 1
                           for r in requests)
    assert stats.steps < sequential_steps
    assert 0.0 < stats.occupancy <= 1.0
    # admission stamps must show slot reuse over time
    assert max(r.admitted_step for r in results.values()) > 0


def test_stack_slice_state_roundtrip(qlm, qfwd):
    """slice_state/stack_state: slicing a mid-decode batch row gives the
    bitwise state of that stream, and stacking slices reassembles the
    batch."""
    params, qlayers, cfg = qlm
    toks = jnp.asarray(
        np.random.default_rng(5).integers(0, cfg.vocab_size, size=(4, 6)),
        jnp.int32)
    state = lstm_lm.init_quant_decode_state(qlayers, 4, per_slot_len=True)
    _, state = qfwd(params, toks, state)

    singles = []
    for r in range(4):
        s1 = lstm_lm.init_quant_decode_state(qlayers, 1, per_slot_len=True)
        _, s1 = qfwd(params, toks[r:r + 1], s1)
        singles.append(s1)
        got = lstm_lm.slice_state(state, r)
        for k in ("h", "c"):
            for a, b in zip(got[k], s1[k]):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    restacked = lstm_lm.stack_state(singles)
    for k in ("h", "c"):
        for a, b in zip(restacked[k], state[k]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(restacked["len"]),
                                  np.asarray(state["len"]))


def test_reset_quant_slot_restores_initial_rows(qlm, qfwd):
    """Admission reset: the reset row equals a freshly-initialized state row
    while other rows are untouched."""
    params, qlayers, cfg = qlm
    state = lstm_lm.init_quant_decode_state(qlayers, 3, per_slot_len=True)
    fresh = jax.tree_util.tree_map(lambda x: np.asarray(x).copy(), state)
    toks = jnp.asarray([[1], [2], [3]], jnp.int32)
    _, state = qfwd(params, toks, state)
    dirty = jax.tree_util.tree_map(lambda x: np.asarray(x).copy(), state)
    state = lstm_lm.reset_quant_slot(qlayers, state, jnp.int32(1))
    for k in ("h", "c"):
        for got, f, d in zip(state[k], fresh[k], dirty[k]):
            got = np.asarray(got)
            np.testing.assert_array_equal(got[1], f[1])
            np.testing.assert_array_equal(got[0], d[0])
            np.testing.assert_array_equal(got[2], d[2])
    assert int(state["len"][1]) == 0 and int(state["len"][0]) == 1


def test_trace_roundtrip(tmp_path, qlm):
    """JSON trace loading: explicit prompts and prompt_len synthesis."""
    import json

    params, qlayers, cfg = qlm
    path = tmp_path / "trace.json"
    path.write_text(json.dumps([
        {"prompt": [3, 1, 4], "gen": 2, "id": 42},
        {"prompt_len": 5, "gen": 3},
    ]))
    reqs = E.load_trace(str(path), cfg.vocab_size, seed=0)
    assert reqs[0].rid == 42 and reqs[0].prompt.tolist() == [3, 1, 4]
    assert reqs[1].prompt.size == 5 and reqs[1].max_new_tokens == 3
    # n_slots=3 reuses the step trace compiled by the tests above
    eng = E.ContinuousBatchingEngine(params, qlayers, cfg, n_slots=3)
    eng.submit_all(reqs)
    results, _ = eng.run()
    assert results[42].tokens == E.decode_single(
        params, qlayers, cfg, reqs[0].prompt, 2)


def test_engine_with_mesh_sharding_hook(qlm):
    """The batch-axis sharding hook (single-device mesh) must not change a
    single emitted token -- including the chunked-prefill program, whose
    (S, K) token block and (S,) valid vector go through
    ``engine_block_sharding``."""
    from jax.sharding import Mesh

    from repro.runtime import sharding as shlib

    params, qlayers, cfg = qlm
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    rules = shlib.rules_for(cfg.shard_profile)
    requests = E.synthetic_trace(4, cfg.vocab_size, seed=2,
                                 prompt_lens=(2, 4), gen_lens=(3,))
    plain = E.ContinuousBatchingEngine(params, qlayers, cfg, n_slots=2,
                                       chunk=2)
    plain.submit_all(requests)
    sharded = E.ContinuousBatchingEngine(params, qlayers, cfg, n_slots=2,
                                         chunk=2, mesh=mesh, rules=rules)
    sharded.submit_all(list(requests))
    rp, _ = plain.run()
    rs, _ = sharded.run()
    assert {k: v.tokens for k, v in rp.items()} == \
        {k: v.tokens for k, v in rs.items()}


# ---------------------------------------------------------------------------
# Chunked prefill: bit-exactness, TTFT metrics, truncation bookkeeping
# ---------------------------------------------------------------------------


def _run_engine(qlm, requests, *, chunk, n_slots=3, max_steps=None):
    params, qlayers, cfg = qlm
    eng = E.ContinuousBatchingEngine(params, qlayers, cfg, n_slots=n_slots,
                                     chunk=chunk)
    # fresh Request objects: engines mutate nothing, but keep inputs isolated
    eng.submit_all([E.Request(rid=r.rid, prompt=r.prompt,
                              max_new_tokens=r.max_new_tokens)
                    for r in requests])
    return eng.run(max_steps=max_steps)


def test_chunked_prefill_bitexact(qlm):
    """Chunk sizes 2 and 4 must emit bit-identical tokens to chunk=1 and to
    decoding each stream alone -- prompts shorter than, equal to, and longer
    than (and not divisible by) the chunk, plus a mid-generation co-tenant,
    all advance correctly in shared steps."""
    params, qlayers, cfg = qlm
    rng = np.random.default_rng(13)
    requests = [
        E.Request(rid=i,
                  prompt=rng.integers(0, cfg.vocab_size, size=(p,)),
                  max_new_tokens=g)
        for i, (p, g) in enumerate(
            [(1, 3), (3, 2), (4, 2), (5, 4), (9, 2), (2, 3)])
    ]
    outs = {}
    for k in (1, 2, 4):
        results, stats = _run_engine(qlm, requests, chunk=k)
        assert stats.chunk == k
        outs[k] = {rid: r.tokens for rid, r in results.items()}
    assert outs[1] == outs[2] == outs[4]
    ref = _reference(params, qlayers, cfg, requests)
    for r in requests:
        assert outs[4][r.rid] == ref[r.rid], f"stream {r.rid} drifted"


def test_chunked_prefill_cuts_ttft_on_prompt_heavy(qlm):
    """Long prompts (>= 16 tokens): chunk=4 must finish prefill in ~P/4
    steps, so total steps and mean TTFT-in-steps drop >= 2x vs chunk=1
    (deterministic -- step counts don't depend on wall clock)."""
    params, qlayers, cfg = qlm
    rng = np.random.default_rng(5)
    requests = [
        E.Request(rid=i,
                  prompt=rng.integers(0, cfg.vocab_size, size=(p,)),
                  max_new_tokens=2)
        for i, p in enumerate([16, 17, 16])
    ]
    _, s1 = _run_engine(qlm, requests, chunk=1)
    _, s4 = _run_engine(qlm, requests, chunk=4)
    assert s4.steps < s1.steps
    assert s1.mean_ttft_steps >= 2 * s4.mean_ttft_steps
    # K=1: TTFT in steps for an immediately-admitted stream is exactly its
    # prompt length (one teacher-forced token per step, first generated
    # token on the step that consumes the last prompt token)
    assert s1.mean_ttft_steps == np.mean([16, 17, 16])


def test_ttft_and_stream_rate_metrics(qlm):
    """Request-level latency bookkeeping: an immediately-admitted stream's
    ttft_steps equals its prompt length at chunk=1, wall-clock fields are
    populated and positive, and stats aggregate them."""
    params, qlayers, cfg = qlm
    rng = np.random.default_rng(9)
    requests = [
        E.Request(rid=i,
                  prompt=rng.integers(0, cfg.vocab_size, size=(p,)),
                  max_new_tokens=3)
        for i, p in enumerate([2, 4, 5])
    ]
    results, stats = _run_engine(qlm, requests, chunk=1)
    for r in requests:
        res = results[r.rid]
        assert res.ttft_steps == r.prompt.size  # admitted at step 0
        assert res.ttft_s is not None and res.ttft_s > 0
        assert res.tokens_per_s is not None and res.tokens_per_s > 0
    assert stats.mean_ttft_steps == np.mean([2, 4, 5])
    assert stats.mean_ttft_s > 0
    assert stats.mean_stream_tokens_per_s > 0


def test_truncation_finished_step_matches_last_ran_step(qlm):
    """max_steps regression: a truncated stream's finished_step must be the
    step that actually ran last (stats.steps - 1), the same stamp a stream
    evicted on that step would get -- not one past it."""
    params, qlayers, cfg = qlm
    rng = np.random.default_rng(3)
    requests = [
        E.Request(rid=i,
                  prompt=rng.integers(0, cfg.vocab_size, size=(2,)),
                  max_new_tokens=8)
        for i in range(3)
    ]
    results, stats = _run_engine(qlm, requests, chunk=1, max_steps=4)
    assert stats.steps == 4
    assert results, "nothing truncated -- workload too short for the test"
    for res in results.values():
        assert res.truncated
        assert res.finished_step == stats.steps - 1
        # partial output: prompt of 2 consumed in 2 steps, tokens on steps
        # 1..3 -> 3 generated of the 8 budgeted
        assert len(res.tokens) == 3
        assert res.ttft_steps == 2


# ---------------------------------------------------------------------------
# Host spans of ``run`` on the profiler's timeline
# ---------------------------------------------------------------------------

# a pass of each kind: step 0 idle (both requests arrive at step 1), two
# head-free chunk advances (remaining 10 and 6 > chunk 4; 9 and 5), a chunk
# step that emits, then one-token steps
_SPAN_WORKLOAD = [(10, 3), (9, 2)]
_PHASES = ("engine.schedule", "engine.feed", "engine.dispatch",
           "engine.sync", "engine.commit")


def _span_requests(cfg):
    rng = np.random.default_rng(21)
    return [E.Request(rid=i,
                      prompt=rng.integers(0, cfg.vocab_size, size=(p,)),
                      max_new_tokens=g, arrival=1)
            for i, (p, g) in enumerate(_SPAN_WORKLOAD)]


def _serve_for_spans(qlm, log_dir=None):
    """Serve the span workload at chunk 4, under a profiler session when
    ``log_dir`` is given; returns ``(tokens by rid, engine.* spans)``, each
    span ``(name, start_ns, end_ns, stats)`` from the host plane."""
    params, qlayers, cfg = qlm
    eng = E.ContinuousBatchingEngine(params, qlayers, cfg, n_slots=3,
                                     chunk=4)
    eng.submit_all(_span_requests(cfg))
    if log_dir is None:
        results, _ = eng.run()
        return {rid: r.tokens for rid, r in results.items()}, []
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # host spans only, not every Python call
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        results, _ = eng.run()
    finally:
        jax.profiler.stop_trace()
    import glob

    from jax.profiler import ProfileData

    path, = glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    s = int(ev.start_ns)
                    spans.append((ev.name, s, s + int(ev.duration_ns),
                                  {k: v for k, v in ev.stats}))
    return ({rid: r.tokens for rid, r in results.items()},
            sorted(spans, key=lambda x: (x[1], -x[2])))


@pytest.fixture(scope="module")
def profiled(qlm, tmp_path_factory):
    return _serve_for_spans(qlm, tmp_path_factory.mktemp("profile"))


def _inside(spans, outer):
    return [sp for sp in spans if sp is not outer
            and outer[1] <= sp[1] and sp[2] <= outer[2]]


def test_run_spans_nest_phases_in_order_inside_each_iteration(profiled):
    """One ``engine.run``; every ``engine.iteration`` lies inside it and
    holds the phases in order: the idle pass only ``schedule``; every other
    pass ``schedule``, ``feed``, ``dispatch``, ``sync`` (only where the
    program emits) and ``commit``, none overlapping."""
    _, spans = profiled
    runs = [sp for sp in spans if sp[0] == "engine.run"]
    assert len(runs) == 1
    iters = [sp for sp in spans if sp[0] == "engine.iteration"]
    assert len(iters) == 1 + 2 + 1 + 2  # idle, advances, chunk step, steps
    assert _inside(spans, runs[0]) == [
        sp for sp in spans if sp[0] != "engine.run"]
    for it in iters:
        inner = _inside(spans, it)
        names = [sp[0] for sp in inner]
        if it is iters[0]:
            assert names == ["engine.schedule"]
            continue
        emits = inner[2][3]["program"] != "chunk_advance"
        assert names == [n for n in _PHASES if emits or n != "engine.sync"]
        assert all(a[2] <= b[1] for a, b in zip(inner, inner[1:]))


def test_dispatch_span_names_the_program(profiled):
    _, spans = profiled
    assert [sp[3]["program"] for sp in spans
            if sp[0] == "engine.dispatch"] == [
        "chunk_advance", "chunk_advance", "chunk_step", "step", "step"]


def test_tokens_identical_with_and_without_a_profiler(qlm, profiled):
    on, spans = profiled
    off, none = _serve_for_spans(qlm)
    assert spans and not none
    assert on == off
    assert [len(on[i]) for i in range(len(_SPAN_WORKLOAD))] == [
        g for _, g in _SPAN_WORKLOAD]


def test_request_and_engine_validation_raises(qlm):
    """Invariants must raise ValueError (not assert, which python -O
    strips): empty prompts, non-positive budgets, bad slot/chunk counts."""
    params, qlayers, cfg = qlm
    with pytest.raises(ValueError, match="empty prompt"):
        E.Request(rid=0, prompt=np.zeros((0,), np.int32), max_new_tokens=1)
    with pytest.raises(ValueError, match="max_new_tokens"):
        E.Request(rid=0, prompt=np.array([1]), max_new_tokens=0)
    with pytest.raises(ValueError, match="n_slots"):
        E.ContinuousBatchingEngine(params, qlayers, cfg, n_slots=0)
    with pytest.raises(ValueError, match="chunk"):
        E.ContinuousBatchingEngine(params, qlayers, cfg, n_slots=1, chunk=0)


def test_load_trace_validates_entries(tmp_path, qlm):
    """Malformed trace entries fail loudly with the entry index, instead of
    KeyError/empty-prompt crashes deep inside the engine."""
    import json

    _, _, cfg = qlm

    def write(payload):
        p = tmp_path / "bad.json"
        p.write_text(json.dumps(payload))
        return str(p)

    cases = [
        ({"not": "a list"}, "expected a JSON list"),
        (["nope"], "entry 0"),
        ([{"prompt_len": 4}], "missing 'gen'"),
        ([{"prompt_len": 4, "gen": 0}], "'gen' must be >= 1"),
        ([{"prompt": [], "gen": 2}], "'prompt' is empty"),
        ([{"prompt_len": 0, "gen": 2}], "'prompt_len' must be >= 1"),
        ([{"gen": 2}], "needs 'prompt' or 'prompt_len'"),
        ([{"prompt_len": 2, "gen": 1}, {"gen": 1}], "entry 1"),
    ]
    for payload, match in cases:
        with pytest.raises(ValueError, match=match):
            E.load_trace(write(payload), cfg.vocab_size)


# ---------------------------------------------------------------------------
# Property test: random workloads + admission orders (hypothesis optional)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:  # the rest of the module must still run without it
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    _WORKLOAD = st.lists(
        st.tuples(st.integers(1, 4), st.integers(1, 4)),  # (prompt_len, gen)
        min_size=1, max_size=6,
    )

    @settings(max_examples=6, deadline=None)
    @given(workload=_WORKLOAD, seed=st.integers(0, 2**16),
           order_seed=st.integers(0, 2**16))
    def test_property_engine_equals_single_stream(qlm, workload, seed,
                                                  order_seed):
        """For random prompt lengths, gen budgets and admission orders,
        every stream's engine tokens are bit-identical to decoding it alone
        (slots fixed at 3 so the jitted step is compiled once per
        module)."""
        params, qlayers, cfg = qlm
        rng = np.random.default_rng(seed)
        requests = [
            E.Request(rid=i,
                      prompt=rng.integers(0, cfg.vocab_size, size=(p,)),
                      max_new_tokens=g)
            for i, (p, g) in enumerate(workload)
        ]
        order = np.random.default_rng(order_seed).permutation(len(requests))
        eng = E.ContinuousBatchingEngine(params, qlayers, cfg, n_slots=3)
        eng.submit_all([requests[i] for i in order])
        results, _ = eng.run()
        for r in requests:
            ref = E.decode_single(params, qlayers, cfg, r.prompt,
                                  r.max_new_tokens)
            assert results[r.rid].tokens == ref, f"stream {r.rid} drifted"

    @settings(max_examples=5, deadline=None)
    @given(workload=_WORKLOAD, chunk=st.integers(1, 8),
           seed=st.integers(0, 2**16), order_seed=st.integers(0, 2**16))
    def test_property_chunked_prefill_bitexact(qlm, workload, chunk, seed,
                                               order_seed):
        """For random chunk sizes K in {1..8}, workloads and admission
        orders, the chunked engine's per-stream tokens are bit-identical to
        the K=1 engine AND to decoding each stream alone (slots fixed at 3
        so chunk programs compile once per distinct K)."""
        params, qlayers, cfg = qlm
        rng = np.random.default_rng(seed)
        requests = [
            E.Request(rid=i,
                      prompt=rng.integers(0, cfg.vocab_size, size=(p,)),
                      max_new_tokens=g)
            for i, (p, g) in enumerate(workload)
        ]
        order = np.random.default_rng(order_seed).permutation(len(requests))
        outs = {}
        for k in sorted({1, chunk}):
            eng = E.ContinuousBatchingEngine(params, qlayers, cfg,
                                             n_slots=3, chunk=k)
            eng.submit_all([requests[i] for i in order])
            results, _ = eng.run()
            outs[k] = {rid: res.tokens for rid, res in results.items()}
        assert outs[1] == outs[chunk]
        for r in requests:
            ref = E.decode_single(params, qlayers, cfg, r.prompt,
                                  r.max_new_tokens)
            assert outs[chunk][r.rid] == ref, f"stream {r.rid} drifted"
