"""Streams the scheduler parked in the host pool during the window
(``EngineStats.preemptions`` summed over its steps), per request admitted
in the window."""


def read(ctx):
    admitted = sum(1 for r in ctx.reqs
                   if r.admit is not None and r.admit <= ctx.close)
    if not admitted:
        return None
    return sum(s.preemptions for s in ctx.window_steps()) / admitted
