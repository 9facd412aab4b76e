"""95th percentile, over every request due in the window, of the time from
its due time to the start of the engine step whose schedule log admits it
(a request never admitted counts as infinitely late).  A traced run leaves
out the requests whose wait overlaps the profiler's collection."""
import math

from harness.metrics import pctl


def read(ctx):
    waits = [(r.admit - r.due) * 1e3 if r.admit is not None else math.inf
             for r in ctx.reqs
             if r.admit is None or ctx.unprofiled(r.due, r.admit)]
    return pctl(waits, 0.95) if waits else None
