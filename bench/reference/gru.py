"""GRU layer, reset-after form with layer-normalized gates, gates
``r, u, n``:

    r  = sigmoid(LN(x W_r + h R_r) * L_r + b_r)
    u  = sigmoid(LN(x W_u + h R_u) * L_u + b_u)
    n  = tanh(LN(x W_n + r * (h R_n)) * L_n + b_n)
    h' = u * h + (1 - u) * n
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import layernorm, mm, quant_act, quant_weight

GATES = ("r", "u", "n")


def layer(p, xs, conf: dict, quant=None):
    if not conf["layernorm"]:
        raise NotImplementedError("reference covers the LN GRU only")
    q = lambda w: quant_weight(w, quant)  # noqa: E731
    W = jnp.concatenate([q(p["W"][g]) for g in GATES], axis=1)
    R = jnp.concatenate([q(p["R"][g]) for g in GATES], axis=1)
    H = p["R"]["r"].shape[1]
    B, _, _ = xs.shape
    ax = mm(quant_act(xs, quant), W)  # (B, T, 3H), hoisted over time

    def gate(a, k, name):
        return layernorm(a[:, k * H:(k + 1) * H], p["L"][name], p["b"][name])

    def step(h, a_x):
        a_h = mm(quant_act(h, quant), R)
        r = jax.nn.sigmoid(gate(a_x + a_h, 0, "r"))
        u = jax.nn.sigmoid(gate(a_x + a_h, 1, "u"))
        a_n = a_x[:, 2 * H:] + r * a_h[:, 2 * H:]
        n = jnp.tanh(layernorm(a_n, p["L"]["n"], p["b"]["n"]))
        h = u * h + (1.0 - u) * n
        return h, h

    _, ys = jax.lax.scan(step, jnp.zeros((B, H), jnp.float32),
                         jnp.swapaxes(ax, 0, 1))
    return jnp.swapaxes(ys, 0, 1)
