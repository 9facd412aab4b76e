"""Roofline share of the persistent sequence kernel in the one-token step
program: its least time (bytes or operations at the chip's peaks, from
shapes) over its summed device time in the trace."""
from harness.kernels import DECODE_PROGRAMS, scan_roofline


def read(ctx):
    return scan_roofline(ctx, DECODE_PROGRAMS, steps=1)
