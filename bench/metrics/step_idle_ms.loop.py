"""Device idle milliseconds per engine iteration while the host was in
``run``'s own time (under ``engine.run`` or ``engine.iteration`` and no
phase span), read from the program's host spans in the trace."""
from harness import phases


def read(ctx):
    return phases.read(ctx, "loop")
