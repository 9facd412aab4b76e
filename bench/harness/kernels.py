"""Shared arithmetic of the kernel and step metrics.

The persistent sequence kernel (``kernels/quant_lstm_scan.py``) runs once
per layer in every model program.  Its ops in the device trace are matched
by ``SCAN_KERNEL``; the programs are told apart by the ``hlo_module`` the
trace gives each op.  A step's share of the peak is read from the same
trace: the programs' operations, from shapes, over their executions'
device time.
"""
from __future__ import annotations

import re

from harness import costs, trace

SCAN_KERNEL = r"quant_recurrent_seq_scan|_scan_kernel"
DECODE_PROGRAMS = r"^jit_step$"
PREFILL_PROGRAMS = r"^jit_chunk_(step|advance)$"


def scan_roofline(ctx, programs: str, steps: int):
    """Least time over measured device time of the kernel's launches in the
    matching programs, in percent; None where the trace has none."""
    if ctx.trace is None or ctx.peaks is None:
        return None
    rx = re.compile(programs)
    measured = launches = 0
    for prog, (sec, n) in trace.kernel_time(ctx.trace, SCAN_KERNEL).items():
        if rx.search(prog):
            measured += sec
            launches += n
    if not launches or measured <= 0:
        return None
    ops, nbytes = costs.scan_kernel_cost(ctx.conf, ctx.conf["n_slots"],
                                         steps)
    least, _ = costs.least_time_s(ops, nbytes, ctx.peaks["int8_ops_per_s"],
                                  ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * launches / measured


def program_ops(conf: dict, program: str):
    """``(int8 ops, bf16 ops)`` of one execution of a model program at its
    compiled shape: every slot's row, ``chunk`` tokens a row in the chunk
    programs; the LM head runs once a row in the one-token and chunk-step
    programs and not at all in the chunk advance."""
    rows = conf["n_slots"]
    tokens = 1 if program == "jit_step" else conf["chunk"]
    head_rows = 0 if program == "jit_chunk_advance" else rows
    return (rows * tokens * costs.int8_ops_per_token(conf),
            head_rows * costs.head_ops_per_row(conf))


def step_mfu(ctx, programs: str):
    """Share of the chip's peak in the matching programs' executions in the
    traced window, in percent: their int8 ops over the int8 peak plus
    their bf16 ops over the bf16 peak, over their summed device time; None
    where the trace has none."""
    if ctx.trace is None or ctx.peaks is None:
        return None
    least = device = 0.0
    for prog, (sec, n) in trace.program_time(ctx.trace, programs).items():
        int8, bf16 = program_ops(ctx.conf, prog)
        least += n * (int8 / ctx.peaks["int8_ops_per_s"]
                      + bf16 / ctx.peaks["bf16_flops_per_s"])
        device += sec
    return 100.0 * least / device if device > 0 else None
