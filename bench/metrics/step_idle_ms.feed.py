"""Device idle milliseconds per engine iteration while the host was in building
the step's inputs (``engine.feed``: drafts, the token, valid and
draft-length arrays, and their puts onto the device), read from the
program's host spans in the trace."""
from harness import phases


def read(ctx):
    return phases.read(ctx, "feed")
