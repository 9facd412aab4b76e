"""Compile-only checks of the persistent sequence kernel for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology and refuses what the chip would refuse
(block tiling, ops Mosaic cannot lower, scoped VMEM).  Interpret mode
checks none of that, so these compiles guard the kernel at the published
widths of the serving models:

* ``lstm-rnnt`` layer: 2048 hidden, LayerNorm, 640 projection;
* ``gru-rnnt`` layer: 2048 input, 2048 hidden, LayerNorm;

each unmasked (prefill / decode) and masked (chunked prefill, the engine),
at B=8 (the engine's slots) and B=1 (``decode_single``); and the LM head's
row blocking, which the compiler must not undo.  Nothing runs, so nothing
here says anything about results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
Keep every such test in this one file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import cell as C
from repro.core import recipe as R
from repro.core.calibrate import Stats, TapCollector
from repro.kernels.quant_lstm_scan import quant_recurrent_seq_scan_pallas
from repro.models import gru as GR
from repro.models import lstm as L
from repro.models import lstm_lm

T = 16  # timesteps; the grid is (T,), so T does not change the kernel


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _calibrated(layer_fn, quantize, params, cfg, d_in):
    xs = jax.random.normal(jax.random.PRNGKey(1), (2, 4, d_in))
    col = TapCollector()
    layer_fn(params, cfg, xs, collector=col)
    stats = Stats()
    stats.merge(jax.device_get(col.snapshot()))
    return quantize(params, cfg, stats)


@pytest.fixture(scope="module")
def layers():
    """One quantized layer per model at its published width (built on the
    host; only the shapes and the static spec reach the compiler)."""
    lstm_cfg = L.LSTMConfig(640, 2048, 640, L.LSTMVariant(
        use_layernorm=True, use_projection=True))
    gru_cfg = GR.GRUConfig(2048, 2048, GR.GRUVariant(use_layernorm=True))
    return {
        "lstm-rnnt": _calibrated(
            L.lstm_layer, R.quantize_lstm_layer,
            L.init_lstm_params(jax.random.PRNGKey(0), lstm_cfg), lstm_cfg,
            640),
        "gru-rnnt": _calibrated(
            GR.gru_layer, R.quantize_gru_layer,
            GR.init_gru_params(jax.random.PRNGKey(0), gru_cfg), gru_cfg,
            2048),
    }


@pytest.mark.parametrize("masked", [False, True], ids=["unmasked", "masked"])
@pytest.mark.parametrize("batch", [8, 1])
@pytest.mark.parametrize("model", ["lstm-rnnt", "gru-rnnt"])
def test_sequence_kernel_compiles_for_v5e(layers, one_chip, model, batch,
                                          masked):
    arrays, spec = layers[model]

    def shape(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    acc = shape((batch, T, arrays["R_cat"].shape[1]), jnp.int32)
    state0 = tuple(shape((batch, leaf.width), leaf.dtype)
                   for leaf in C.get_cell(spec).state_leaves(spec))
    valid_len = shape((batch,), jnp.int32) if masked else None
    weights = jax.tree_util.tree_map(
        lambda a: shape(a.shape, a.dtype), arrays)

    compiled = jax.jit(
        lambda a, x, s, v: quant_recurrent_seq_scan_pallas(a, spec, x, s, v)
    ).lower(weights, acc, state0, valid_len).compile()

    assert "tpu_custom_call" in compiled.as_text()
    out = compiled.memory_analysis().output_size_in_bytes
    assert out >= batch * T * C.get_cell(spec).d_out(spec)  # ys int8


@pytest.mark.parametrize("rows", [1, 8, 64])
def test_lm_head_compiles_to_8_row_matmuls_for_v5e(one_chip, rows):
    """The integer LM's head runs every row through an 8-row matmul on the
    chip, so a row's logits do not depend on how many rows a program
    holds (decode_single's one row, the engine's slots, a prompt)."""
    head = jax.ShapeDtypeStruct((640, 4096), jnp.bfloat16, sharding=one_chip)
    x = jax.ShapeDtypeStruct((rows, 640), jnp.float32, sharding=one_chip)
    hlo = jax.jit(
        lambda w, x: lstm_lm._quant_head({"lm_head": w}, x)
    ).lower(head, x).compile().as_text()
    matmuls = re.findall(r"= (bf16\[[\d,]*\])\S* (?:convolution|dot)\(",
                         hlo)
    assert matmuls and set(matmuls) == {"bf16[8,4096]"}, matmuls
