"""A whole benchmark run at a CPU size, skipping only the look for a chip,
with the timed path broken underneath: ``correct`` must come out false.

The faults are planted through the model functions the engine's programs
call (``lstm_lm.quant_forward`` for the one-token step,
``quant_chunk_step`` and ``quant_chunk_advance`` for chunked prefill);
``jax.clear_caches`` makes the programs trace again with them."""
import os

import jax
import jax.numpy as jnp
import pytest

from harness import cell, loop
from repro.models import lstm_lm

TINY = os.path.join(os.path.dirname(__file__), "data", "tiny")
BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "bench")


def run(workload, seed, tmp_path):
    paths = cell.Paths(spec=os.path.join(TINY, "BENCHMARK.json"), data=TINY,
                       metrics=os.path.join(BENCH, "metrics"),
                       trace_dir=str(tmp_path / "trace"))
    return cell.run(paths, workload, seed, 3.0, False, loop.clock(),
                    require_chip=False, backend="xla", log=lambda m: None)


@pytest.fixture
def fresh_programs():
    jax.clear_caches()
    yield
    jax.clear_caches()


def alter_token(mp):
    """Every one-token step's greedy token becomes its vocabulary
    neighbour."""
    orig = lstm_lm.quant_forward

    def forward(*a, **kw):
        logits, states = orig(*a, **kw)
        return jnp.roll(logits, 1, axis=-1), states

    mp.setattr(lstm_lm, "quant_forward", forward)


def keep_state(mp):
    """Every step returns the state it was given."""
    for name, pos in (("quant_forward", 4), ("quant_chunk_step", 4),
                      ("quant_chunk_advance", 4)):
        orig = getattr(lstm_lm, name)

        def fn(*a, _orig=orig, _pos=pos, **kw):
            out = _orig(*a, **kw)
            states = a[_pos]
            return states if not isinstance(out, tuple) else \
                (out[0], states)

        mp.setattr(lstm_lm, name, fn)


def test_sound_run_is_correct(tmp_path, fresh_programs):
    """The long-prompt stand-in end to end (``test_bench_check`` serves
    the chat one on three seeds)."""
    result = run("tiny-gru.tprompt", 3000000017, tmp_path)
    assert result["correct"], result["check"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "check"


@pytest.mark.parametrize("fault", [alter_token, keep_state])
def test_broken_timed_path_is_not_correct(fault, tmp_path, monkeypatch,
                                          fresh_programs):
    fault(monkeypatch)
    result = run("tiny-lstm.tchat", 3000000017, tmp_path)
    assert not result["correct"], result["check"]
