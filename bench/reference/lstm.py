"""LSTM layer with layer-normalized gates and a projection (Sak et al.;
arXiv:2101.05453 eqs 1-7), gates ``i, f, z, o``, no peephole, no CIFG:

    a_g = x W_g + h R_g
    g   = act(LN(a_g) * L_g + b_g)     act = sigmoid, z: tanh
    c'  = i * z + f * c
    m   = o * tanh(c')
    h'  = m W_proj + b_proj
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import layernorm, mm, quant_act, quant_weight

GATES = ("i", "f", "z", "o")


def layer(p, xs, conf: dict, quant=None):
    if conf.get("peephole") or conf.get("cifg") or not conf["layernorm"] \
            or not conf["projection"]:
        raise NotImplementedError("reference covers LN + projection only")
    q = lambda w: quant_weight(w, quant)  # noqa: E731
    W = jnp.concatenate([q(p["W"][g]) for g in GATES], axis=1)
    R = jnp.concatenate([q(p["R"][g]) for g in GATES], axis=1)
    Wp = q(p["W_proj"])
    H = p["R"]["i"].shape[1]
    B, _, _ = xs.shape
    ax = mm(quant_act(xs, quant), W)  # (B, T, 4H), hoisted over time

    def step(carry, a_x):
        h, c = carry
        a = a_x + mm(quant_act(h, quant), R)
        g = {name: layernorm(a[:, k * H:(k + 1) * H], p["L"][name],
                             p["b"][name])
             for k, name in enumerate(GATES)}
        c = jax.nn.sigmoid(g["i"]) * jnp.tanh(g["z"]) \
            + jax.nn.sigmoid(g["f"]) * c
        m = jax.nn.sigmoid(g["o"]) * jnp.tanh(c)
        h = mm(quant_act(m, quant), Wp) + p["b_proj"]
        return (h, c), h

    init = (jnp.zeros((B, Wp.shape[1]), jnp.float32),
            jnp.zeros((B, H), jnp.float32))
    _, ys = jax.lax.scan(step, init, jnp.swapaxes(ax, 0, 1))
    return jnp.swapaxes(ys, 0, 1)
