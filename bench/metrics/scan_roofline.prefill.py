"""As ``scan_roofline.decode``, for the kernel's launches in the chunk-step
and chunk-advance programs (one launch covers ``chunk`` tokens)."""
from harness.kernels import PREFILL_PROGRAMS, scan_roofline


def read(ctx):
    return scan_roofline(ctx, PREFILL_PROGRAMS, steps=ctx.conf["chunk"])
