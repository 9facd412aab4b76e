"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

The traced window is the harness's own ``harness.window`` host span.  On
each device plane (``/device:TPU:<n>``) the ``XLA Ops`` line holds one
event per operation the device ran, named by its HLO instruction
(``%quant_recurrent_seq_scan_pallas.13 = (...) custom-call(...)``); the
``XLA Modules`` line holds one event per program execution
(``jit_step(<fingerprint>)``).  Each op belongs to the program execution
that contains its start.

* busy: the union of the op intervals inside the window;
* idle share: ``1 - busy / window``;
* kernel time per program: the summed durations of the ops whose name
  matches a kernel's pattern, grouped by program;
* program time: the summed durations of a program's executions;
* idle gaps: the gaps in the busy union, each split among the harness's
  host spans that overlap it.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import gzip
import os
import re
from typing import Dict, List, Optional, Tuple

HOST_SPANS = ("harness.submit", "engine.step", "harness.stamp",
              "harness.wait")
WINDOW_SPAN = "harness.window"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: int  # ns
    end: int  # ns
    program: str = ""


@dataclasses.dataclass
class Trace:
    window: Tuple[int, int]
    devices: Dict[str, List[Event]]  # device plane -> ops, by start
    programs: Dict[str, List[Event]]  # device plane -> program executions
    host: List[Event]  # harness spans, by start


def find(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _program_name(name: str) -> str:
    """``jit_step(3622636557912285044)`` -> ``jit_step``."""
    return re.sub(r"\(\d+\)$", "", name)


def op_name(name: str) -> str:
    """``%fusion.12 = s8[...] fusion(...)`` -> ``fusion``."""
    m = re.match(r"%?([^\s=]+?)(?:\.\d+)?\s*=", name)
    return m.group(1) if m else name


def load(path: str) -> Trace:
    """Reduce an ``.xplane.pb`` file (or its gzip, ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    devices, programs, host = {}, {}, []
    window = None
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            lines = {line.name: line for line in plane.lines}
            ops = []
            for ev in lines["XLA Ops"].events if "XLA Ops" in lines else ():
                s = int(ev.start_ns)
                ops.append(Event(op_name(ev.name), s,
                                 s + int(ev.duration_ns)))
            mods = []
            for ev in (lines["XLA Modules"].events
                       if "XLA Modules" in lines else ()):
                s = int(ev.start_ns)
                mods.append(Event(_program_name(ev.name), s,
                                  s + int(ev.duration_ns)))
            devices[plane.name] = sorted(ops, key=lambda e: e.start)
            programs[plane.name] = sorted(mods, key=lambda e: e.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        s = int(ev.start_ns)
                        window = (s, s + int(ev.duration_ns))
                    elif ev.name in HOST_SPANS:
                        s = int(ev.start_ns)
                        host.append(Event(ev.name, s,
                                          s + int(ev.duration_ns)))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span")
    _tag_programs(devices, programs)
    return Trace(window, devices, programs,
                 sorted(host, key=lambda e: e.start))


def _tag_programs(devices, programs) -> None:
    """Give each op the program whose execution contains its start."""
    for plane, ops in devices.items():
        mods = programs.get(plane, [])
        j = 0
        for i, op in enumerate(ops):
            if op.program or not mods:
                continue
            while j + 1 < len(mods) and mods[j + 1].start <= op.start:
                j += 1
            m = mods[j]
            if m.start <= op.start < m.end:
                ops[i] = dataclasses.replace(op, program=m.name)


def _clip(events, window):
    lo, hi = window
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            yield s, t


def busy_intervals(events, window) -> List[Tuple[int, int]]:
    """Union of the events' intervals inside the window, merged, sorted."""
    out: List[List[int]] = []
    for s, t in sorted(_clip(events, window)):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def busy_s(trace: Trace) -> float:
    """Busy seconds in the window, averaged over the devices."""
    per = [sum(t - s for s, t in busy_intervals(ops, trace.window))
           for ops in trace.devices.values()]
    return sum(per) / len(per) / 1e9 if per else 0.0


def window_s(trace: Trace) -> float:
    return (trace.window[1] - trace.window[0]) / 1e9


def idle_share(trace: Trace) -> Optional[float]:
    if not trace.devices:
        return None
    return 1.0 - busy_s(trace) / window_s(trace)


def kernel_time(trace: Trace, pattern: str) -> Dict[str, Tuple[float, int]]:
    """{program: (seconds, launches)} of the ops whose name matches
    ``pattern``, inside the window, summed over the devices."""
    rx = re.compile(pattern)
    out: Dict[str, List[float]] = collections.defaultdict(lambda: [0.0, 0])
    for ops in trace.devices.values():
        for op in ops:
            if rx.search(op.name) and trace.window[0] <= op.start \
                    and op.end <= trace.window[1]:
                acc = out[op.program]
                acc[0] += (op.end - op.start) / 1e9
                acc[1] += 1
    return {k: (v[0], v[1]) for k, v in out.items()}


def program_time(trace: Trace,
                 pattern: str) -> Dict[str, Tuple[float, int]]:
    """{program: (seconds, executions)} of the program executions whose name
    matches ``pattern`` and that lie wholly inside the window, summed over
    the devices."""
    rx = re.compile(pattern)
    out: Dict[str, List[float]] = collections.defaultdict(lambda: [0.0, 0])
    for mods in trace.programs.values():
        for m in mods:
            if rx.search(m.name) and trace.window[0] <= m.start \
                    and m.end <= trace.window[1]:
                acc = out[m.name]
                acc[0] += (m.end - m.start) / 1e9
                acc[1] += 1
    return {k: (v[0], v[1]) for k, v in out.items()}


def top_ops(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` ``program:op`` names that took the most device time in the
    window, averaged over the devices."""
    tot: Dict[str, float] = collections.defaultdict(float)
    for ops in trace.devices.values():
        for s, t, name in ((max(o.start, trace.window[0]),
                            min(o.end, trace.window[1]),
                            f"{o.program}:{o.name}") for o in ops):
            if t > s:
                tot[name] += (t - s) / 1e9
    k = max(len(trace.devices), 1)
    return sorted(((name, v / k) for name, v in tot.items()),
                  key=lambda x: -x[1])[:n]


def idle_by_host(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """Idle device seconds in the window by what the host was doing: each
    gap of the busy union is split among the harness spans that overlap it
    (``host.other`` for the part none does), summed per span name, averaged
    over the devices."""
    tot: Dict[str, float] = collections.defaultdict(float)
    host = trace.host
    starts = [h.start for h in host]
    for ops in trace.devices.values():
        busy = busy_intervals(ops, trace.window)
        edges = [trace.window[0]] + [x for iv in busy for x in iv] \
            + [trace.window[1]]
        for s, t in zip(edges[::2], edges[1::2]):
            if t <= s:
                continue
            share: Dict[str, int] = collections.defaultdict(int)
            i = max(bisect.bisect_right(starts, s) - 1, 0)
            while i < len(host) and host[i].start < t:
                h = host[i]
                o = min(h.end, t) - max(h.start, s)
                if o > 0:
                    share[h.name] += o
                i += 1
            covered = sum(share.values())
            share["host.other"] += max(0, (t - s) - covered)
            for name, ns in share.items():
                tot[name] += ns / 1e9
    k = max(len(trace.devices), 1)
    return sorted(((name, v / k) for name, v in tot.items() if v > 0),
                  key=lambda x: -x[1])[:n]
