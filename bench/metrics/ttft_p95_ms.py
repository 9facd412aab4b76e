"""95th percentile, over every request due in the window, of the time from
its due time to its first generated token on the host clock; a request
that never produced one counts as infinitely late."""
import math

from harness.metrics import pctl


def read(ctx):
    ttft = [(r.stamps[0] - r.due) * 1e3 if r.stamps else math.inf
            for r in ctx.reqs]
    return pctl(ttft, 0.95) if ttft else None
