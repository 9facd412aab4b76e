"""Device idle time by the phase of the engine's step that the host was in.

``ContinuousBatchingEngine.run`` marks its own phases with host spans
(``PROGRAM_SPANS``): ``engine.run`` around the call, ``engine.iteration``
around each pass of its loop, and inside a pass ``engine.schedule``,
``engine.feed``, ``engine.dispatch``, ``engine.sync`` and ``engine.commit``.
They lie on the profiler's host plane, on the clock of the device planes.

On each device every gap of the busy union inside the traced window (the
gaps ``trace.idle_by_host`` splits) is given, piece by piece, to the
innermost program span over it: a phase's own span, or ``loop`` for
``run``'s self time (under ``engine.run`` or ``engine.iteration`` and no
phase).  A phase's idle time is summed over the window, divided by the
number of ``engine.iteration`` spans that start in it, and averaged over
the devices (``split``).

``trace.load`` keeps only the harness's spans, so the program spans are read
from the same ``.xplane.pb`` here, once per trace.
"""
from __future__ import annotations

import bisect
import collections
import gzip
import os
from typing import Dict, List, Optional, Tuple

from harness import trace
from harness.trace import Event

ITERATION = "engine.iteration"
# each program span, and what its idle time counts as
PHASE_OF = {"engine.run": "loop", "engine.iteration": "loop",
            "engine.schedule": "schedule", "engine.feed": "feed",
            "engine.dispatch": "dispatch", "engine.sync": "sync",
            "engine.commit": "commit"}
PROGRAM_SPANS = tuple(PHASE_OF)
PHASES = ("schedule", "feed", "dispatch", "sync", "commit", "loop")
# where ``bench/run.py`` has the profiler write a --trace 1 run's profile
TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".bench_trace")


def load_spans(path: str) -> Tuple[Optional[Tuple[int, int]], List[Event]]:
    """``(harness.window, program spans by start)`` of an ``.xplane.pb``
    file (or its gzip)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    window, spans = None, []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == trace.WINDOW_SPAN:
                    s = int(ev.start_ns)
                    window = (s, s + int(ev.duration_ns))
                elif ev.name in PROGRAM_SPANS:
                    s = int(ev.start_ns)
                    spans.append(Event(ev.name, s, s + int(ev.duration_ns)))
    return window, sorted(spans, key=lambda e: (e.start, -e.end))


def spans_of(tr: trace.Trace,
             trace_dir: Optional[str] = None) -> List[Event]:
    """The program spans of the profile in ``trace_dir`` (``TRACE_DIR``
    by default) whose ``harness.window`` is ``tr``'s; empty where there is
    none."""
    try:
        found, spans = load_spans(trace.find(trace_dir or TRACE_DIR))
    except FileNotFoundError:
        return []
    return spans if found == tr.window else []


def innermost(spans: List[Event]) -> List[Tuple[int, int, str]]:
    """``(start, end, name)`` pieces of time, by start, each named by the
    innermost of the nested spans over it; time under none is left out."""
    out: List[Tuple[int, int, str]] = []
    stack: List[Event] = []  # open spans, outermost first
    at = 0

    def close_until(t: int) -> None:
        nonlocal at
        while stack and stack[-1].end <= t:
            top = stack.pop()
            if top.end > at:
                out.append((at, top.end, top.name))
                at = top.end
        if stack and t > at:
            out.append((at, t, stack[-1].name))
        at = max(at, t)

    for sp in sorted(spans, key=lambda e: (e.start, -e.end)):
        close_until(sp.start)
        stack.append(sp)
    close_until(max((sp.end for sp in spans), default=0))
    return out


def idle_by_phase(tr: trace.Trace, spans: List[Event]) -> Dict[str, float]:
    """Idle device seconds in the window by phase (``PHASES``), summed over
    the window and averaged over the devices."""
    pieces = innermost(spans)
    starts = [p[0] for p in pieces]
    tot: Dict[str, float] = collections.defaultdict(float)
    for ops in tr.devices.values():
        busy = trace.busy_intervals(ops, tr.window)
        edges = [tr.window[0]] + [x for iv in busy for x in iv] \
            + [tr.window[1]]
        for s, t in zip(edges[::2], edges[1::2]):
            i = max(bisect.bisect_right(starts, s) - 1, 0)
            while i < len(pieces) and pieces[i][0] < t:
                a, b, name = pieces[i]
                o = min(b, t) - max(a, s)
                if o > 0:
                    tot[PHASE_OF[name]] += o / 1e9
                i += 1
    k = max(len(tr.devices), 1)
    return {p: tot[p] / k for p in PHASES}


def iterations(tr: trace.Trace, spans: List[Event]) -> int:
    """``engine.iteration`` spans that start in the window."""
    lo, hi = tr.window
    return sum(1 for sp in spans
               if sp.name == ITERATION and lo <= sp.start < hi)


def split(tr: trace.Trace, spans: List[Event]) -> Optional[Dict[str, float]]:
    """Idle device milliseconds per engine iteration in the window, by phase;
    None where no iteration starts in it or no device ran."""
    n = iterations(tr, spans)
    if not n or not tr.devices:
        return None
    return {p: 1e3 * v / n for p, v in idle_by_phase(tr, spans).items()}


# the newest trace's split, ``(id, window) -> {phase: ms}``: the six readers
# of a run share one read of its profile and one pass over its ops
_SPLIT: Dict[Tuple[int, Tuple[int, int]], Dict[str, float]] = {}


def read(ctx, phase: str) -> Optional[float]:
    """A ``step_idle_ms.<phase>`` reader: None without a trace, or where the
    program left no spans in it."""
    tr = ctx.trace
    if tr is None:
        return None
    key = (id(tr), tr.window)
    if key not in _SPLIT:
        _SPLIT.clear()
        _SPLIT[key] = split(tr, spans_of(tr)) or {}
    return _SPLIT[key].get(phase)
