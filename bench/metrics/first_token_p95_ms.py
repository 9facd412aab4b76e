"""95th percentile, over every request due in the window, of the time from
its due time to its first generated token on the host clock: the p95 time
to first token, read per layer where host stalls spread it too widely for
a bound (a request that never produced one counts as infinitely late).  A
traced run leaves out the requests whose wait overlaps the profiler's
collection."""
import math

from harness.metrics import pctl


def read(ctx):
    ttft = [(r.stamps[0] - r.due) * 1e3 if r.stamps else math.inf
            for r in ctx.reqs
            if not r.stamps or ctx.unprofiled(r.due, r.stamps[0])]
    return pctl(ttft, 0.95) if ttft else None
