"""Share of the chip's peak in the one-token step program, from the device
trace: each execution's int8 stack operations (every slot's row) over the
int8 peak plus its bf16 LM-head operations over the bf16 peak, summed and
divided by the executions' summed device time."""
from harness.kernels import DECODE_PROGRAMS, step_mfu


def read(ctx):
    return step_mfu(ctx, DECODE_PROGRAMS)
