"""Build a configuration's served model from the seed, through the program's
public entry points: ``model_zoo.build`` for the parameter tree,
``lstm_lm.quantize_stack`` for calibration and quantization, and
``ContinuousBatchingEngine`` for serving.

The float weights and the calibration tokens are the benchmark's own: one
jitted call draws them on the device from the configuration's
``weights_seed``, into the tree the program's ``init`` would return (read
with ``jax.eval_shape``, which runs nothing).  The reference reads the same
float weights, and nothing that the program made from them.

The weights are fixed per configuration, as a deployment serves one model;
a run's ``--seed`` draws its traffic.  Calibration bakes the model's scales
into the compiled programs as constants, so weights drawn anew for every
run would make every run compile from scratch.
"""
from __future__ import annotations

import dataclasses
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

SIZE_KEYS = ("n_layers", "d_model", "d_rnn", "vocab_size")


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for a configuration file, its sizes
    taken from the file."""
    from repro.configs.registry import CONFIGS
    from repro.models import lstm_lm

    cfg = dataclasses.replace(CONFIGS[conf["arch"]],
                              **{k: conf[k] for k in SIZE_KEYS})
    if lstm_lm.rnn_cell(cfg) != conf["cell"]:
        raise ValueError(f"{conf['name']}: the program runs cell "
                         f"{lstm_lm.rnn_cell(cfg)!r}, the file states "
                         f"{conf['cell']!r}")
    want = conf["d_proj"] if conf["cell"] == "lstm" else conf["d_rnn"]
    if lstm_lm.stack_d_out(cfg) != want:
        raise ValueError(f"{conf['name']}: the program's layer output width "
                         f"is {lstm_lm.stack_d_out(cfg)}, the file states "
                         f"{want}")
    return cfg


def seed_key(seed: int):
    """A PRNG key from any whole seed; every bit of it counts (a plain
    ``PRNGKey`` keeps only the low 32 bits without 64-bit mode)."""
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def _leaf(key, path, shape, init: dict):
    """Value of one parameter, by the configuration's ``init``: input
    matrices ``N(0, 1/fan_in)``, recurrent matrices (``R``) ``recurrent_gain``
    times that, LN gains 1, biases 0; embedding and head normal with their
    stated deviations, in their served dtype."""
    name = jax.tree_util.keystr(path)
    key = jax.random.fold_in(key, zlib.crc32(name.encode()))
    std = {"['embedding']": init["embedding_std"],
           "['lm_head']": init["head_std"]}.get(name)
    if std is not None:
        return (jax.random.normal(key, shape.shape, jnp.float32)
                * std).astype(shape.dtype)
    last = getattr(path[-1], "key", None)
    parent = getattr(path[-2], "key", None) if len(path) > 1 else None
    if shape.ndim == 2:
        gain = init["recurrent_gain"] if parent == "R" else 1.0
        w = jax.random.normal(key, shape.shape, jnp.float32)
        return (w * gain / math.sqrt(shape.shape[0])).astype(shape.dtype)
    if parent == "L":
        return jnp.ones(shape.shape, shape.dtype)
    if parent == "b" or last == "b_proj":
        return jnp.zeros(shape.shape, shape.dtype)
    raise ValueError(f"no rule to draw parameter {name} {shape}")


def make_weights(cfg, conf: dict):
    """``(params, calib_tokens)`` drawn on the device in one jitted call."""
    from repro.models import model_zoo

    bundle = model_zoo.build(cfg)
    key = seed_key(conf["weights_seed"])
    tree = jax.eval_shape(lambda k: bundle.init(k)[0], jax.random.PRNGKey(0))
    cal = conf["calibration"]

    @jax.jit
    def draw(key):
        k_w, k_c = jax.random.split(key)
        params = jax.tree_util.tree_map_with_path(
            lambda p, s: _leaf(k_w, p, s, conf["init"]), tree)
        calib = jax.random.randint(k_c, (cal["batch"], cal["tokens"]), 0,
                                   conf["vocab_size"], jnp.int32)
        return params, calib

    return draw(key)


def quantize(params, cfg, calib):
    from repro.models import lstm_lm

    return lstm_lm.quantize_stack(params, cfg, calib)


def engine(params, qlayers, cfg, conf: dict, traffic: dict, backend: str):
    from repro.launch.engine import ContinuousBatchingEngine

    return ContinuousBatchingEngine(
        params, qlayers, cfg, n_slots=conf["n_slots"], backend=backend,
        chunk=conf["chunk"], policy=traffic["policy"],
        oversubscribe=traffic["oversubscribe"])
