"""Whether what the timed path served is right, against the plain reference.

Once the window has closed, a sample of the finished requests, drawn from
the seed and always holding the longest, is teacher-forced through the
float32 reference (``bench/reference``): each prompt followed by the tokens
the engine served for it.  At every position where the engine served a
token, the gap is how far that token's reference logit lies below the
reference's best logit there.  Two numbers come of the gaps, the widest
(``max_logit_gap``) and the mean over the positions compared
(``mean_logit_gap``); a cell compares those that its limits file
(``bench/limits/<cell>.json``) names under ``compare``, each with its own
limit.  A greedy
token that the reference also ranks first has gap 0; a token that lost a
near-tie to rounding has a small gap; a wrong token has a gap of the order
of the logits' spread.

The control puts the reference itself, computed one precision step lower
(int4 weights and activations, ``reference.forward(quant=4)``), in the
program's place: at each of the same positions it takes the token the
control ranks first, and reads that token's gap in the float reference.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def sample(reqs, seed: int, n: int) -> List[Tuple[np.ndarray, List[int]]]:
    """``n`` finished requests drawn from the seed, the longest included,
    as ``(prompt, served tokens)``."""
    done = sorted((r for r in reqs if r.tokens and not r.truncated),
                  key=lambda r: r.rid)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.tokens), -r.rid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 7])
    pick = [longest] + [rest[i] for i in rng.permutation(len(rest))[:n - 1]]
    return [(r.prompt, list(r.tokens)) for r in pick]


def blocks(samples, n: int, length: int):
    """Token and target blocks ``(n, length)``: row b holds prompt + served
    tokens but the last; the target at position ``t`` is the served token
    that followed it (-1 where none)."""
    toks = np.zeros((n, length), np.int32)
    tgt = np.full((n, length), -1, np.int32)
    for b, (prompt, served) in enumerate(samples):
        row = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        if row.size > length:
            raise ValueError(f"request of {row.size} tokens exceeds the "
                             f"check's block length {length}")
        toks[b, :row.size] = row
        p = prompt.size
        tgt[b, p - 1:p - 1 + len(served)] = served
    return toks, tgt


class Reference:
    """The reference's compiled gap program for one block shape."""

    def __init__(self, conf: dict, quant: Optional[int] = None):
        import reference

        self._fn = jax.jit(
            lambda params, toks, tgt: reference.gaps(params, toks, tgt,
                                                     conf, quant))

    def __call__(self, params, toks, tgt):
        return jax.device_get(self._fn(params, jnp.asarray(toks),
                                       jnp.asarray(tgt)))


def gap_numbers(top, tgt_logit, tgt) -> dict:
    """Widest and mean gap over the positions with a target, and how many
    there are."""
    mask = tgt >= 0
    gaps = (top - tgt_logit)[mask]
    if not gaps.size:
        return {"max_logit_gap": float("nan"),
                "mean_logit_gap": float("nan"), "compared": 0}
    return {"max_logit_gap": float(gaps.max()),
            "mean_logit_gap": float(gaps.mean()), "compared": int(gaps.size)}


def served_gap(ref: Reference, params, samples, n: int, length: int):
    """Gap numbers of the served tokens, and how many served tokens differ
    from the reference's argmax."""
    toks, tgt = blocks(samples, n, length)
    top, tl, arg = ref(params, toks, tgt)
    return gap_numbers(top, tl, tgt), int(((arg != tgt) & (tgt >= 0)).sum())


def control_gap(ref: Reference, control: Reference, params, samples,
                n: int, length: int) -> dict:
    """Gap numbers, in the float reference, of the tokens the control ranks
    first at the served positions."""
    toks, tgt = blocks(samples, n, length)
    _, _, arg = control(params, toks, tgt)
    picked = np.where(tgt >= 0, arg, -1)
    top, tl, _ = ref(params, toks, picked)
    return gap_numbers(top, tl, picked)


def verdict(numbers: Sequence[Tuple[str, float, float]]) -> bool:
    """True when every ``(name, value, limit)`` is within its limit; a value
    that is not a number fails."""
    return all(np.isfinite(v) and v <= lim for _, v, lim in numbers)
