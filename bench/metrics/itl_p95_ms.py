"""95th percentile over every gap between consecutive output tokens of
every request due in the window (a parked stream's gap includes its time
in the pool)."""
import numpy as np

from harness.metrics import pctl


def read(ctx):
    gaps = [g * 1e3 for r in ctx.reqs for g in np.diff(r.stamps)]
    return pctl(gaps, 0.95) if gaps else None
