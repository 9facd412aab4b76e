"""Plain float32 reference of the stacked recurrent LM.

It imports nothing of the program.  It reads the float weights that the
benchmark drew from the seed (``harness.model.make_weights``), in the tree
the model's parameters take (``embedding``, ``lm_head``, and per layer
``W``/``R``/``b``/``L`` per gate, plus the LSTM's ``W_proj``/``b_proj``),
and runs the published equations layer by layer over whole token blocks in
``jax.numpy`` at float32 with ``HIGHEST`` matmul precision: no kernel, no
cache, no batching of streams into slots, no integer arithmetic.

The cell of each configuration lives in ``reference/<cell>.py`` and gives
``layer(p, xs, conf, quant) -> ys``.

``quant`` makes the control: the same equations with the matrices and the
matmul inputs rounded to ``bits`` (symmetric per-tensor weights, asymmetric
per-tensor activations), the precision one step below the configuration's.
"""
from __future__ import annotations

import importlib
from typing import Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def layernorm(a, gain, bias):
    mu = jnp.mean(a, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(a - mu), axis=-1, keepdims=True)
    return (a - mu) * jax.lax.rsqrt(var + 1e-12) * gain + bias


def quant_weight(w, bits: Optional[int]):
    """Round ``w`` to ``bits``-bit symmetric per-tensor levels."""
    if bits is None:
        return w
    top = 2 ** (bits - 1) - 1
    s = jnp.max(jnp.abs(w)) / top
    return jnp.clip(jnp.round(w / s), -top, top) * s


def quant_act(x, bits: Optional[int]):
    """Round ``x`` to ``bits``-bit asymmetric levels over its own range."""
    if bits is None:
        return x
    lo, hi = jnp.min(x), jnp.max(x)
    s = jnp.maximum(hi - lo, 1e-12) / (2 ** bits - 1)
    return jnp.round((x - lo) / s) * s + lo


def cell_module(cell: str):
    return importlib.import_module(f"reference.{cell}")


def forward(params, tokens, conf: dict, quant: Optional[int] = None):
    """Float logits ``(B, T, V)`` of teacher-forcing ``tokens (B, T)`` from
    the zero state."""
    layer = cell_module(conf["cell"]).layer
    x = params["embedding"].astype(jnp.float32)[tokens]
    for p in params["lstm"]:
        x = layer(p, x, conf, quant)
    x = quant_act(x, quant)
    head = quant_weight(params["lm_head"].astype(jnp.float32), quant)
    return mm(x, head)


def gaps(params, tokens, targets, conf: dict, quant: Optional[int] = None):
    """Per position: the best logit, the logit of ``targets`` (clipped to
    the vocabulary where negative) and the argmax token."""
    logits = forward(params, tokens, conf, quant)
    top = jnp.max(logits, axis=-1)
    tgt = jnp.take_along_axis(
        logits, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
    return top, tgt, jnp.argmax(logits, axis=-1).astype(jnp.int32)
