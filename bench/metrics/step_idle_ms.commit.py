"""Device idle milliseconds per engine iteration while the host was in the
per-slot bookkeeping after a step (``engine.commit``: emitted tokens,
finished streams, the watchdog), read from the program's host spans in the
trace."""
from harness import phases


def read(ctx):
    return phases.read(ctx, "commit")
