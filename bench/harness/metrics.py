"""Finding and running metric readers.

Each metric named in ``BENCHMARK.json`` has a reader of its own,
``bench/metrics/<name>.py``, with ``read(ctx) -> float | None``.  ``ctx``
is a :class:`Context`.  A reader that finds nothing to read returns None,
and the metric is left out of the result line.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import math
import os
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np


@dataclasses.dataclass
class Context:
    conf: dict  # the configuration file
    traffic: dict  # the traffic file
    seconds: float  # the measured window's length
    t0: float  # host clock at the window's start
    reqs: list  # loop.Req, one per request due in the window
    steps: list  # loop.Step, every engine step, window and drain
    setup_s: float
    peaks: Optional[dict] = None  # harness.peaks entry, None off the chip
    trace: Any = None  # harness.trace.Trace of a --trace 1 run
    # host-clock span in which the profiler collected, in a --trace 1 run
    profiled: Optional[Tuple[float, float]] = None

    @property
    def close(self) -> float:
        return self.t0 + self.seconds

    def window_steps(self) -> List[Any]:
        return [s for s in self.steps if s.t1 <= self.close]

    def unprofiled(self, start: float, end: float) -> bool:
        """Whether ``[start, end]`` misses the profiler's collection, so a
        host-clock time over it holds none of the profiler's cost."""
        return self.profiled is None or end < self.profiled[0] \
            or start > self.profiled[1]


def pctl(values: Sequence[float], q: float) -> float:
    """Nearest-rank ``q``-quantile (``0 < q <= 1``); inf counts as a value."""
    xs = sorted(values)
    if not xs:
        return float("nan")
    return xs[max(0, math.ceil(q * len(xs)) - 1)]


def reader(metrics_dir: str, name: str):
    path = os.path.join(metrics_dir, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_all(metrics_dir: str, metrics: Sequence[dict], ctx: Context) -> dict:
    out = {}
    for m in metrics:
        v = reader(metrics_dir, m["name"])(ctx)
        if v is not None and np.isfinite(v):
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
