"""Device idle milliseconds per engine iteration while the host was in the
scheduler's phase (``engine.schedule``: the policy, and each park, resume
and admit), read from the program's host spans in the trace."""
from harness import phases


def read(ctx):
    return phases.read(ctx, "schedule")
