"""Mean host time of the window's engine steps in which every live stream
was generating (the one-token program; each syncs on its greedy tokens):
their total time over their count.  A traced run leaves out the steps that
overlap the profiler's collection."""


def read(ctx):
    steps = [s for s in ctx.window_steps()
             if s.decode_only and ctx.unprofiled(s.t0, s.t1)]
    if not steps:
        return None
    return sum(s.t1 - s.t0 for s in steps) / len(steps) * 1e3
