"""Device idle milliseconds per engine iteration while the host was in reading
the program's outputs back to the host (``engine.sync``; absent on head-free
chunk advances), read from the program's host spans in the trace."""
from harness import phases


def read(ctx):
    return phases.read(ctx, "sync")
