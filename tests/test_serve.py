"""Serving correctness: prefill/decode agreement, int8 path, ring buffers."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import SMOKE_CONFIGS
from repro.models import model_zoo, quant_transformer

IDENT = lambda x, logical=None: x


def _greedy_from_decode(bundle, params, prompt, n_steps, max_len=64):
    state = bundle.init_state(prompt.shape[0], max_len)
    logits = None
    for i in range(prompt.shape[1]):
        logits, state = bundle.decode(params, prompt[:, i:i+1], state, IDENT)
    return logits


@pytest.mark.parametrize("name", ["qwen3-4b", "stablelm-1.6b", "internvl2-2b"])
def test_prefill_decode_consistency(name):
    """Teacher-forcing the prompt through decode must reproduce the prefill
    logits (cache write/read correctness)."""
    cfg = SMOKE_CONFIGS[name]
    bundle = model_zoo.build(cfg)
    params, _ = bundle.init(jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0,
                                cfg.vocab_size)
    batch = {"tokens": prompt}
    if cfg.family == "vlm":
        pytest.skip("vlm prefill prepends patch embeds; decode-only path")
    lp = bundle.prefill(params, batch, IDENT)
    ld = _greedy_from_decode(bundle, params, prompt, 0)
    np.testing.assert_allclose(
        np.asarray(lp, np.float32), np.asarray(ld, np.float32),
        rtol=0.1, atol=0.15)


def test_int8_weightonly_close_to_float():
    cfg = SMOKE_CONFIGS["qwen3-4b"]
    bundle = model_zoo.build(cfg)
    params, _ = bundle.init(jax.random.PRNGKey(0))
    qb = quant_transformer.quantize_bundle(bundle)
    qparams, _ = qb.init(jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                cfg.vocab_size)
    pf = jax.nn.softmax(bundle.prefill(params, {"tokens": prompt}, IDENT))
    pq = jax.nn.softmax(qb.prefill(qparams, {"tokens": prompt}, IDENT))
    assert float(jnp.abs(pf - pq).max()) < 5e-3


def test_int8_kv_cache_decode():
    cfg = SMOKE_CONFIGS["qwen3-4b"]
    bundle = model_zoo.build(cfg)
    params, _ = bundle.init(jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                cfg.vocab_size)
    # float cache
    sf = bundle.init_state(2, 32)
    # int8 cache
    sq = bundle.init_state(2, 32, quantized=True)
    for i in range(prompt.shape[1]):
        lf, sf = bundle.decode(params, prompt[:, i:i+1], sf, IDENT)
        lq, sq = bundle.decode(params, prompt[:, i:i+1], sq, IDENT)
    pf, pq = jax.nn.softmax(lf), jax.nn.softmax(lq)
    assert float(jnp.abs(pf - pq).max()) < 2e-2
    assert sq["main"]["k"].dtype == jnp.int8


def test_sliding_window_ring_buffer():
    """Decode past the window size must keep only the last W positions."""
    import dataclasses
    cfg = dataclasses.replace(SMOKE_CONFIGS["qwen3-4b"], attn_window=8)
    bundle = model_zoo.build(cfg)
    params, _ = bundle.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 20), 0,
                              cfg.vocab_size)
    state = bundle.init_state(1, 8)  # cache only as deep as the window
    for i in range(20):
        logits, state = bundle.decode(params, toks[:, i:i+1], state, IDENT)
    assert bool(jnp.isfinite(logits).all())
    assert int(state["len"]) == 20


def test_int8_lstm_serving_state_continuity():
    """Integer-only serving: one-shot scanned prefill must produce exactly
    the logits of step-by-step decode (integer math is deterministic, so this
    is a bitwise check on the carried int8/int16 states)."""
    from repro.models import lstm_lm

    cfg = SMOKE_CONFIGS["lstm-rnnt"]
    bundle = model_zoo.build(cfg)
    params, _ = bundle.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 7), 0,
                              cfg.vocab_size)
    qlayers = lstm_lm.quantize_stack(params, cfg, toks)
    prefill = jax.jit(lambda p, t, s: lstm_lm.quant_prefill(
        p, qlayers, cfg, t, s))
    decode = jax.jit(lambda p, t, s: lstm_lm.quant_decode_step(
        p, qlayers, cfg, t, s))
    lp, sp = prefill(params, toks, lstm_lm.init_quant_decode_state(qlayers, 2))
    state = lstm_lm.init_quant_decode_state(qlayers, 2)
    for i in range(toks.shape[1]):
        ld, state = decode(params, toks[:, i:i + 1], state)
    for k in ("h", "c"):
        for a, b in zip(sp[k], state[k]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(lp, np.float32),
                               np.asarray(ld, np.float32), rtol=1e-5,
                               atol=1e-5)


def test_lstm_serving_state_continuity():
    cfg = SMOKE_CONFIGS["lstm-rnnt"]
    bundle = model_zoo.build(cfg)
    params, _ = bundle.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0, cfg.vocab_size)
    # one-shot prefill logits == step-by-step decode logits
    lp = bundle.prefill(params, {"tokens": toks}, IDENT)
    state = bundle.init_state(2, 16)
    for i in range(9):
        ld, state = bundle.decode(params, toks[:, i:i+1], state, IDENT)
    np.testing.assert_allclose(np.asarray(lp, np.float32),
                               np.asarray(ld, np.float32), rtol=2e-2,
                               atol=2e-2)


def test_compile_cache_uses_env_dir_as_set(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, serving uses it and sets
    nothing in code."""
    from repro.launch import serve

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert serve.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_repo_dir(monkeypatch):
    """Without the variable the cache goes to a fixed <repo>/.jax_cache."""
    import os

    from repro.launch import serve

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    try:
        path = serve.enable_compile_cache()
        assert path == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
