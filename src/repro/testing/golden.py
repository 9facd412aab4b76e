"""Deterministic golden-case builders for the integer bit-exactness
regression harness (cell-agnostic since PR 8: LSTM and GRU).

Integer decode is fully deterministic, so small golden outputs (int8/int16
tensors and greedy tokens) can be checked into the repo and asserted with
exact equality: any refactor of the fused executor, the recipe, or the
serving engine that silently changes even one low bit fails loudly.

Three golden families:

* **Per-variant layer cases** -- all 16 LSTM topology variants of the paper
  (LN x Proj x PH x CIFG), and both GRU variants (LN x), run through the
  cell-agnostic ``quant_recurrent_layer`` on a fixed seeded input; the
  golden records the full int8 output sequence and every final state leaf.
* **LM decode case** -- a smoke stack (``lstm-rnnt`` or ``gru-rnnt``)
  end-to-end: scanned prefill + greedy decode; the golden records the
  generated token ids and the final per-layer state leaves.
* **Engine decode cases** (GRU goldens) -- a fixed mixed-length workload
  through the continuous-batching engine under a scheduling policy +
  oversubscription ratio; the golden records every stream's emitted tokens
  (which are also asserted against ``decode_single`` in the tests).

Scale derivation happens in float64 numpy offline and calibration runs a
float32 jax forward; both are deterministic for a fixed platform/jax build
(the goldens are generated on the CPU CI platform).  Everything after the
recipe is integer-only and platform-independent.

Regenerate with ``python tests/golden/regen_goldens.py`` after an
*intentional* numerics change, and say so in the commit message.
"""
from __future__ import annotations

import json
from typing import Any, Dict, List, Tuple

import jax
import numpy as np

from repro.core import cell as C
from repro.core import recipe as R
from repro.core.calibrate import Stats, TapCollector
from repro.models import gru as GR
from repro.models import lstm as L
from repro.models import quant_lstm as QL

# layer-case dims: small enough for a readable JSON diff, big enough to
# exercise packed-matmul tiling and the integer LayerNorm limb math
B, T, D_IN, D_H, D_P = 2, 5, 8, 12, 6

LM_PROMPT_LEN = 6
LM_GEN = 8


def _golden_prng():
    """The PRNG mode the checked-in goldens were drawn under.

    JAX 0.5 flipped the default of ``jax_threefry_partitionable``, which
    changes every ``jax.random`` draw for the same key.  Pinning the old
    mode keeps the golden inputs and weights -- and so the checked-in
    integers -- independent of the installed JAX.
    """
    return jax.threefry_partitionable(False)


def variant_key(variant: L.LSTMVariant) -> str:
    return variant.name


def build_variant_case(variant: L.LSTMVariant, seed: int = 0):
    """Deterministic quantized layer + input for one topology variant."""
    cfg = L.LSTMConfig(D_IN, D_H, D_P if variant.use_projection else 0,
                       variant)
    with _golden_prng():
        params = L.init_lstm_params(jax.random.PRNGKey(seed), cfg)
        xs = 0.8 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                     (B, T, D_IN))
    col = TapCollector()
    L.lstm_layer(params, cfg, xs, collector=col)
    stats = Stats()
    stats.merge(jax.device_get(col.snapshot()))
    arrays, spec = R.quantize_lstm_layer(params, cfg, stats)
    xs_q = QL.quantize_input(xs, spec.s_x, spec.zp_x)
    return xs_q, arrays, spec


def execute_case(case, backend: str) -> Dict[str, Any]:
    """Run a built layer case; returns JSON-ready int lists: the output
    sequence under ``"ys"`` plus one entry per final state leaf (LSTM
    ``{"h", "c"}``, GRU ``{"h"}``) -- the pre-PR-8 LSTM schema unchanged."""
    xs_q, arrays, spec = case
    run = jax.jit(lambda a, x: QL.quant_recurrent_layer(
        a, spec, x, backend=backend))
    ys_q, state = run(arrays, xs_q)
    out = {"ys": np.asarray(ys_q).astype(int).tolist()}
    for key, leaf in zip(C.get_cell(spec).state_keys(spec), state):
        out[key] = np.asarray(leaf).astype(int).tolist()
    return out


def run_variant_case(variant: L.LSTMVariant, backend: str = "xla"
                     ) -> Dict[str, Any]:
    """Build + execute one layer case (regen entry point)."""
    return execute_case(build_variant_case(variant), backend)


def gru_variant_key(variant: GR.GRUVariant) -> str:
    return variant.name


def build_gru_variant_case(variant: GR.GRUVariant, seed: int = 0):
    """Deterministic quantized GRU layer + input for one variant."""
    cfg = GR.GRUConfig(D_IN, D_H, variant)
    with _golden_prng():
        params = GR.init_gru_params(jax.random.PRNGKey(seed), cfg)
        xs = 0.8 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                     (B, T, D_IN))
    col = TapCollector()
    GR.gru_layer(params, cfg, xs, collector=col)
    stats = Stats()
    stats.merge(jax.device_get(col.snapshot()))
    arrays, spec = R.quantize_gru_layer(params, cfg, stats)
    xs_q = QL.quantize_input(xs, spec.s_x, spec.zp_x)
    return xs_q, arrays, spec


def run_gru_variant_case(variant: GR.GRUVariant, backend: str = "xla"
                         ) -> Dict[str, Any]:
    """Build + execute one GRU layer case (regen entry point)."""
    return execute_case(build_gru_variant_case(variant), backend)


def build_lm_case(arch: str = "lstm-rnnt"
                  ) -> Tuple[Any, Any, Any, np.ndarray]:
    """Deterministic quantized smoke recurrent LM + prompt (params,
    qlayers, cfg, prompt)."""
    from repro.configs.registry import SMOKE_CONFIGS
    from repro.models import lstm_lm, model_zoo

    cfg = SMOKE_CONFIGS[arch]
    bundle = model_zoo.build(cfg)
    with _golden_prng():
        params, _ = bundle.init(jax.random.PRNGKey(0))
        calib = jax.random.randint(jax.random.PRNGKey(2), (4, 8), 0,
                                   cfg.vocab_size)
    qlayers = lstm_lm.quantize_stack(params, cfg, calib)
    prompt = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(LM_PROMPT_LEN,)).astype(np.int32)
    return params, qlayers, cfg, prompt


def run_lm_case(backend: str = "xla", arch: str = "lstm-rnnt",
                built=None) -> Dict[str, Any]:
    """Greedy-decode the LM case (``built``: a ``build_lm_case`` result to
    reuse); returns {tokens, <state leaves...>} int lists (LSTM:
    {tokens, h, c}; GRU: {tokens, h})."""
    import jax.numpy as jnp

    from repro.models import lstm_lm

    params, qlayers, cfg, prompt = built or build_lm_case(arch)
    prefill = jax.jit(lambda p, t, s: lstm_lm.quant_prefill(
        p, qlayers, cfg, t, s, backend=backend))
    decode = jax.jit(lambda p, t, s: lstm_lm.quant_decode_step(
        p, qlayers, cfg, t, s, backend=backend))
    state = lstm_lm.init_quant_decode_state(qlayers, 1)
    logits, state = prefill(params, jnp.asarray(prompt[None]), state)
    tokens = [int(jnp.argmax(logits, -1)[0])]
    for _ in range(LM_GEN - 1):
        tok = jnp.asarray([[tokens[-1]]], jnp.int32)
        logits, state = decode(params, tok, state)
        tokens.append(int(jnp.argmax(logits, -1)[0]))
    out: Dict[str, Any] = {"tokens": tokens}
    for key in (k for k in state if k != "len"):
        out[key] = [np.asarray(leaf).astype(int).tolist()
                    for leaf in state[key]]
    return out


# fixed engine-golden workload: mixed prompt/gen lengths, enough streams to
# force preemption at oversubscribe=2.0 with 4 slots
ENGINE_SLOTS = 4
ENGINE_REQUESTS = 8


def engine_trace(cfg):
    from repro.launch import engine as E

    return E.synthetic_trace(
        ENGINE_REQUESTS, cfg.vocab_size, seed=11,
        prompt_lens=(3, 5, 8), gen_lens=(4, 6, 9))


def run_engine_case(arch: str, policy: str, oversubscribe: float,
                    backend: str = "xla", built=None) -> Dict[str, Any]:
    """Serve the fixed workload through the engine; returns each stream's
    emitted tokens keyed by request id (JSON keys are strings)."""
    from repro.launch import engine as E

    params, qlayers, cfg, _ = built or build_lm_case(arch)
    requests = engine_trace(cfg)
    eng = E.ContinuousBatchingEngine(
        params, qlayers, cfg, n_slots=ENGINE_SLOTS, backend=backend,
        policy=policy, oversubscribe=oversubscribe)
    eng.submit_all(requests)
    results, _ = eng.run()
    return {str(rid): list(res.tokens) for rid, res in sorted(
        results.items())}


def generate_goldens() -> Dict[str, Any]:
    """All LSTM golden cases, generated on the xla backend."""
    out: Dict[str, Any] = {"variants": {}, "lm": run_lm_case(backend="xla")}
    for variant in L.ALL_VARIANTS:
        out["variants"][variant_key(variant)] = run_variant_case(
            variant, backend="xla")
    return out


# engine goldens cover both a plain policy and a preempting one under
# oversubscription -- the pool/preemption path must stay bit-stable too
ENGINE_GOLDEN_CASES = (("fifo", 1.0), ("srf", 2.0))


def generate_gru_goldens() -> Dict[str, Any]:
    """All GRU golden cases (layer variants + LM decode + engine decode),
    generated on the xla backend."""
    out: Dict[str, Any] = {
        "variants": {},
        "lm": run_lm_case(backend="xla", arch="gru-rnnt"),
    }
    for variant in GR.ALL_VARIANTS:
        out["variants"][gru_variant_key(variant)] = run_gru_variant_case(
            variant, backend="xla")
    built = build_lm_case("gru-rnnt")
    out["engine"] = {
        f"{policy}-{ratio}": run_engine_case(
            "gru-rnnt", policy, ratio, backend="xla", built=built)
        for policy, ratio in ENGINE_GOLDEN_CASES
    }
    return out


def write_goldens(path: str, generate=generate_goldens) -> None:
    with open(path, "w") as f:
        json.dump(generate(), f, separators=(",", ":"))
        f.write("\n")


def load_goldens(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)
