"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals / window)."""
from harness import trace


def read(ctx):
    if ctx.trace is None:
        return None
    share = trace.idle_share(ctx.trace)
    return None if share is None else 100.0 * share
