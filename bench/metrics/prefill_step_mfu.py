"""As ``decode_step_mfu``, for the chunk-step and chunk-advance programs
(``chunk`` tokens of every slot's row an execution; the advance runs no LM
head)."""
from harness.kernels import PREFILL_PROGRAMS, step_mfu


def read(ctx):
    return step_mfu(ctx, PREFILL_PROGRAMS)
