"""Device idle milliseconds per engine iteration while the host was in the call
of the jitted step program (``engine.dispatch``, until it returns), read
from the program's host spans in the trace."""
from harness import phases


def read(ctx):
    return phases.read(ctx, "dispatch")
