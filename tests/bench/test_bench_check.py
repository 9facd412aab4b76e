"""The check that decides ``correct``, at a CPU size: sound runs pass, and
the control -- the reference computed at int4 in the program's place --
fails.  The tiny cell (``data/tiny``) is a CPU-sized stand-in of
``lstm-rnnt.chat``; its limit was set from CPU readings at that size."""
import json
import os

import jax

from harness import cell, check, loop

TINY = os.path.join(os.path.dirname(__file__), "data", "tiny")


def load(kind, name):
    with open(os.path.join(TINY, kind, f"{name}.json")) as f:
        return json.load(f)


def test_sound_runs_pass_and_the_int4_control_fails():
    conf, mix = load("configs", "tiny-lstm"), load("traffic", "tchat")
    limits = load("limits", "tiny-lstm.tchat")["compare"]
    jax.clear_caches()
    params, engine = cell.build(conf, mix, "xla", lambda m: None)
    n, length = mix["check"]["requests"], mix["check"]["length"]
    ref = check.Reference(conf)
    control = check.Reference(conf, quant=4)
    for seed in (2, 4, 5):
        arrivals = cell.schedule(mix, 3.0, seed, conf)
        feed = cell.serve_window(engine, arrivals, loop.clock(), 3.0)
        assert all(r.tokens is not None for r in feed.reqs)
        samples = check.sample(feed.reqs, seed, n)
        gaps, _ = check.served_gap(ref, params, samples, n, length)
        assert gaps["compared"] > 0
        assert all(gaps[k] <= v["limit"] for k, v in limits.items()), gaps
        ctl = check.control_gap(ref, control, params, samples, n, length)
        assert any(ctl[k] > v["limit"] for k, v in limits.items()), ctl


def test_blocks_line_up_served_tokens_with_their_positions():
    import numpy as np

    toks, tgt = check.blocks([(np.array([5, 6, 7], np.int32), [8, 9])], 2, 6)
    assert toks.tolist() == [[5, 6, 7, 8, 0, 0], [0] * 6]
    assert tgt.tolist() == [[-1, -1, 8, 9, -1, -1], [-1] * 6]
