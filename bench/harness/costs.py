"""Operations and bytes of the integer recurrent LM, from shapes alone.

Everything here is computed from a configuration file's sizes
(``bench/configs/<name>.json``), never read from the program, so no change
to the program can change what a roofline is measured against.

Shapes of one layer (``cell`` = ``lstm`` or ``gru``; ``G`` gates of width
``H = d_rnn``; ``d_out`` = the layer's output width: the projection for the
LSTM, ``H`` for the GRU; ``d_in`` = ``d_model`` for layer 0, ``d_out``
above it):

* input GEMM (hoisted out of the recurrence): ``W_cat`` int8 ``d_in x G*H``
* recurrent GEMM, inside the sequence kernel: ``R_cat`` int8 ``d_out x G*H``
* LSTM projection, inside the kernel: ``W_proj`` int8 ``H x d_out``
* LM head: bf16 ``d_out x vocab``

An operation is a multiply or an add, so one multiply-accumulate is 2.
"""
from __future__ import annotations

GATES = {"lstm": 4, "gru": 3}


def d_out(conf: dict) -> int:
    return conf["d_proj"] if conf["cell"] == "lstm" else conf["d_rnn"]


def _gh(conf: dict) -> int:
    return GATES[conf["cell"]] * conf["d_rnn"]


def _proj(conf: dict) -> int:
    """Elements of the in-kernel projection (0 for a cell without one)."""
    return conf["d_rnn"] * conf["d_proj"] if conf["cell"] == "lstm" else 0


def layer_weight_bytes(conf: dict, layer: int) -> int:
    """int8 bytes of one layer's weights: input GEMM + recurrent GEMM +
    projection."""
    d_in = conf["d_model"] if layer == 0 else d_out(conf)
    return d_in * _gh(conf) + d_out(conf) * _gh(conf) + _proj(conf)


def stack_weight_bytes(conf: dict) -> int:
    return sum(layer_weight_bytes(conf, i) for i in range(conf["n_layers"]))


def head_bytes(conf: dict) -> int:
    return d_out(conf) * conf["vocab_size"] * 2  # bf16


def step_weight_bytes(conf: dict) -> int:
    """Weight bytes one decode step streams from HBM: the stack and the
    head, each read once whatever the batch."""
    return stack_weight_bytes(conf) + head_bytes(conf)


def int8_ops_per_token(conf: dict) -> int:
    """int8 operations of the recurrent stack for one token of one stream."""
    return 2 * stack_weight_bytes(conf)


def head_ops_per_row(conf: dict) -> int:
    """bf16 operations of the LM head for one row."""
    return 2 * d_out(conf) * conf["vocab_size"]


def scan_kernel_cost(conf: dict, batch: int, steps: int):
    """``(ops, bytes)`` of one launch of the persistent sequence kernel for
    one layer over a ``(batch, steps)`` block.

    The kernel pads its batch to whole 8-row tiles and computes every row.
    It reads ``R_cat`` (and ``W_proj``) from HBM once per launch, and per
    step reads the hoisted int32 input accumulator ``(B, G*H)`` and writes
    the int8 output ``(B, d_out)``; the carried state is read and written
    once.  Small per-gate vectors are left out, so the bytes are a lower
    bound and the roofline share can only be understated.
    """
    b = -(-batch // 8) * 8
    gh = _gh(conf)
    ops = 2 * b * steps * (d_out(conf) * gh + _proj(conf))
    weights = d_out(conf) * gh + _proj(conf)
    stream = steps * b * (gh * 4 + d_out(conf))
    state = 2 * state_bytes_per_stream_layer(conf) * b
    return ops, weights + stream + state


def state_bytes_per_stream_layer(conf: dict) -> int:
    """Carried integer state of one stream in one layer: int8 output, plus
    the LSTM's int16 cell."""
    if conf["cell"] == "lstm":
        return d_out(conf) + 2 * conf["d_rnn"]
    return conf["d_rnn"]


def least_time_s(ops: float, nbytes: float, peak_ops: float,
                 peak_bytes_per_s: float):
    """``(seconds, bound)``: the least time the chip could take, and
    whether bandwidth (``"bytes"``) or compute (``"ops"``) sets it."""
    t_ops, t_bytes = ops / peak_ops, nbytes / peak_bytes_per_s
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
