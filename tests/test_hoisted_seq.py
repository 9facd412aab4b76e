"""Hoisted-GEMM sequence executors + persistent Pallas sequence kernel.

PR-4 acceptance gates:
  * the hoisted executor (ONE time-batched input GEMM outside the scan) is
    bit-exact with the pre-hoist per-step scan (`quant_lstm_seq_stepwise`)
    for all 16 topology variants, on `xla` AND through the persistent
    Pallas sequence kernel (`interpret`);
  * the input GEMM is genuinely hoisted: the scan body of the hoisted
    executor carries ONE fewer dot_general than the stepwise body;
  * `quant_lstm_seq_masked` ragged bit-exactness holds for arbitrary
    valid-length vectors (hypothesis property) on both lowerings;
  * backend-name validation raises `ValueError` (survives `python -O`).
Goldens replay (numerics untouched) is covered by tests/test_golden_lstm.py.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import recipe as R
from repro.core.calibrate import Stats, TapCollector
from repro.kernels import ops
from repro.models import lstm as L
from repro.models import quant_lstm as QL

pytestmark = pytest.mark.fast

B, T, D_IN, D_H, D_P = 4, 6, 16, 24, 12


def _setup(variant, seed=0, b=B, t=T):
    cfg = L.LSTMConfig(D_IN, D_H, D_P if variant.use_projection else 0,
                       variant)
    params = L.init_lstm_params(jax.random.PRNGKey(seed), cfg)
    xs = 0.8 * jax.random.normal(jax.random.PRNGKey(seed + 1), (b, t, D_IN))
    col = TapCollector()
    L.lstm_layer(params, cfg, xs, collector=col)
    stats = Stats()
    stats.merge(jax.device_get(col.snapshot()))
    arrays, spec = R.quantize_lstm_layer(params, cfg, stats)
    return QL.quantize_input(xs, spec.s_x, spec.zp_x), arrays, spec


def _state(spec, b=B):
    d_out = spec.cfg_d_proj if spec.use_projection else spec.cfg_d_hidden
    h0 = jnp.full((b, d_out), spec.zp_h_out, jnp.int8)
    c0 = jnp.zeros((b, spec.cfg_d_hidden), jnp.int16)
    return h0, c0


@pytest.mark.parametrize("variant", L.ALL_VARIANTS, ids=lambda v: v.name)
def test_hoisted_matches_stepwise_and_kernel_all_variants(variant):
    """stepwise/xla == hoisted/xla == persistent-kernel/interpret, bit for
    bit, including the final (h, c) carries (the PR-4 acceptance gate)."""
    xs_q, arrays, spec = _setup(variant)
    h0, c0 = _state(spec)
    y_s, (h_s, c_s) = ops.quant_lstm_seq_stepwise(
        arrays, spec, xs_q, h0, c0, backend="xla")
    y_h, (h_h, c_h) = ops.quant_lstm_seq(
        arrays, spec, xs_q, h0, c0, backend="xla")
    y_k, (h_k, c_k) = ops.quant_lstm_seq(
        arrays, spec, xs_q, h0, c0, backend="interpret")
    for got, want in ((y_h, y_s), (h_h, h_s), (c_h, c_s),
                      (y_k, y_s), (h_k, h_s), (c_k, c_s)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("b", [1, 9])
def test_kernel_batch_padding_keeps_rows_exact(b):
    """The persistent kernel runs its batch padded to whole 8-row tiles;
    B=1 (``decode_single``'s shape) and B=9 still match ``xla`` bit for
    bit, unmasked and masked."""
    variant = L.LSTMVariant(use_layernorm=True, use_projection=True)
    xs_q, arrays, spec = _setup(variant, b=b)
    h0, c0 = _state(spec, b=b)
    valid = jnp.arange(b, dtype=jnp.int32) % (T + 1)
    for run in (
        lambda be: ops.quant_lstm_seq(arrays, spec, xs_q, h0, c0,
                                      backend=be),
        lambda be: ops.quant_lstm_seq_masked(arrays, spec, xs_q, h0, c0,
                                             valid, backend=be),
    ):
        want = jax.tree_util.tree_leaves(run("xla"))
        got = jax.tree_util.tree_leaves(run("interpret"))
        assert [g.shape for g in got] == [w.shape for w in want]
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _count_dot_generals(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            n += 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", None)
                if inner is not None:
                    n += _count_dot_generals(inner)
    return n


def _scan_body_dot_generals(jaxpr) -> int:
    """dot_general count inside the (single) lax.scan body of ``jaxpr``."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            return _count_dot_generals(eqn.params["jaxpr"].jaxpr)
    raise AssertionError("no scan primitive found")


def test_input_gemm_hoisted_out_of_scan_body():
    """The hoisted executor's scan body runs ONLY the recurrent matmul (1
    dot_general; + projection when enabled), while the stepwise baseline
    still carries the input GEMM per step."""
    variant = L.LSTMVariant()  # no projection: gate matmuls only
    xs_q, arrays, spec = _setup(variant)
    h0, c0 = _state(spec)
    hoisted = jax.make_jaxpr(
        lambda a, x: ops.quant_lstm_seq(a, spec, x, h0, c0, backend="xla")
    )(arrays, xs_q)
    stepwise = jax.make_jaxpr(
        lambda a, x: ops.quant_lstm_seq_stepwise(
            a, spec, x, h0, c0, backend="xla")
    )(arrays, xs_q)
    assert _scan_body_dot_generals(hoisted.jaxpr) == 1
    assert _scan_body_dot_generals(stepwise.jaxpr) == 2
    # the hoisted GEMM still exists -- once, outside the scan
    assert _count_dot_generals(hoisted.jaxpr) == 2


def test_masked_hoisted_matches_prefix_feeding():
    """Deterministic ragged check on both lowerings: each row's final state
    after a masked (B, T) block == feeding only its valid prefix."""
    variant = L.LSTMVariant(use_layernorm=True, use_projection=True)
    xs_q, arrays, spec = _setup(variant)
    valid = jnp.asarray([0, 1, 4, 6], jnp.int32)
    h0, c0 = _state(spec)
    for backend in ("xla", "interpret"):
        ys_m, (h_m, c_m) = ops.quant_lstm_seq_masked(
            arrays, spec, xs_q, h0, c0, valid, backend=backend)
        for row, n in enumerate(np.asarray(valid)):
            if n == 0:
                np.testing.assert_array_equal(np.asarray(h_m)[row],
                                              np.asarray(h0)[row])
                np.testing.assert_array_equal(np.asarray(c_m)[row],
                                              np.asarray(c0)[row])
                continue
            ys_r, (h_r, c_r) = ops.quant_lstm_seq(
                arrays, spec, xs_q[row:row + 1, :n],
                h0[row:row + 1], c0[row:row + 1], backend="xla")
            np.testing.assert_array_equal(np.asarray(h_m)[row],
                                          np.asarray(h_r)[0])
            np.testing.assert_array_equal(np.asarray(c_m)[row],
                                          np.asarray(c_r)[0])
            np.testing.assert_array_equal(np.asarray(ys_m)[row, :n],
                                          np.asarray(ys_r)[0])


def test_masked_ragged_valid_lens_property():
    """Hypothesis property: for ANY per-row valid-length vector in [0, T],
    the masked hoisted executor's final state matches unmasked prefix
    feeding row by row (bitwise), and the persistent-kernel lowering
    (interpret) agrees with the xla scan on every sampled vector."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    variant = L.LSTMVariant(use_layernorm=True, use_projection=True)
    xs_q, arrays, spec = _setup(variant, seed=7)
    h0, c0 = _state(spec)
    run_masked = jax.jit(lambda v: ops.quant_lstm_seq_masked(
        arrays, spec, xs_q, h0, c0, v, backend="xla"))
    # one compile (fixed shapes); each example only re-executes the kernel
    run_masked_kernel = jax.jit(lambda v: ops.quant_lstm_seq_masked(
        arrays, spec, xs_q, h0, c0, v, backend="interpret"))
    # specializes per prefix length n (n <= T, so at most T programs)
    run_prefix = jax.jit(lambda x, h, c: ops.quant_lstm_seq(
        arrays, spec, x, h, c, backend="xla"))

    @settings(max_examples=8, deadline=None)
    @given(st.lists(st.integers(min_value=0, max_value=T),
                    min_size=B, max_size=B))
    def prop(valid_lens):
        valid = jnp.asarray(valid_lens, jnp.int32)
        ys_m, (h_m, c_m) = run_masked(valid)
        ys_k, (h_k, c_k) = run_masked_kernel(valid)
        np.testing.assert_array_equal(np.asarray(ys_m), np.asarray(ys_k))
        np.testing.assert_array_equal(np.asarray(h_m), np.asarray(h_k))
        np.testing.assert_array_equal(np.asarray(c_m), np.asarray(c_k))
        for row, n in enumerate(valid_lens):
            if n == 0:
                h_r, c_r = h0[row:row + 1], c0[row:row + 1]
            else:
                _, (h_r, c_r) = run_prefix(
                    xs_q[row:row + 1, :n], h0[row:row + 1], c0[row:row + 1])
            np.testing.assert_array_equal(np.asarray(h_m)[row],
                                          np.asarray(h_r)[0])
            np.testing.assert_array_equal(np.asarray(c_m)[row],
                                          np.asarray(c_r)[0])

    prop()


def test_empty_sequence_returns_carry_unchanged():
    """T == 0 regression: the pre-hoist executor returned the carry
    untouched; the hoisted paths (reshape + grid=(T,) kernel) must too,
    on every backend."""
    variant = L.LSTMVariant()
    xs_q, arrays, spec = _setup(variant)
    h0, c0 = _state(spec)
    empty = xs_q[:, :0]
    for backend in ("xla", "interpret"):
        ys, (h, c) = ops.quant_lstm_seq(
            arrays, spec, empty, h0, c0, backend=backend)
        assert ys.shape == (B, 0, D_H)
        np.testing.assert_array_equal(np.asarray(h), np.asarray(h0))
        np.testing.assert_array_equal(np.asarray(c), np.asarray(c0))
        ys_m, (h_m, c_m) = ops.quant_lstm_seq_masked(
            arrays, spec, empty, h0, c0,
            jnp.zeros((B,), jnp.int32), backend=backend)
        assert ys_m.shape == (B, 0, D_H)
        np.testing.assert_array_equal(np.asarray(h_m), np.asarray(h0))
        np.testing.assert_array_equal(np.asarray(c_m), np.asarray(c0))


def test_set_backend_rejects_unknown_names():
    """Bugfix regression: validation must be a plain raise (assert would be
    stripped under ``python -O``) and must name the valid backends."""
    prev = ops.get_backend()
    try:
        with pytest.raises(ValueError, match="pallas_interpret"):
            ops.set_backend("cuda")
        assert ops.get_backend() == prev  # rejected names leave it untouched
    finally:
        ops.set_backend(prev)


def test_resolve_rejects_unknown_backend_kwarg():
    """Per-call ``backend=`` goes through the same ValueError validation."""
    variant = L.LSTMVariant()
    xs_q, arrays, spec = _setup(variant)
    h0, c0 = _state(spec)
    with pytest.raises(ValueError, match="valid backends"):
        ops.quant_lstm_seq(arrays, spec, xs_q, h0, c0, backend="cuda")
