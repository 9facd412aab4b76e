"""Open-loop driving of the engine on the host clock.

The window drives ``ContinuousBatchingEngine.run(max_steps=1,
keep_live=True)`` step by step, the entry ``FleetRouter`` drives its shards
through.  Each request is submitted once its wall-clock due time has passed
(engine ``arrival`` 0, so the engine admits it as soon as a slot is free);
each new token is stamped on the host clock when the step that made it
returns.  The loop sleeps only when nothing is live or queued, so a slow
step delays later requests instead of slowing the offered load.

Every harness call sits in a ``jax.profiler.TraceAnnotation`` span
(``harness.submit``, ``engine.step``, ``harness.stamp``,
``harness.wait``), so a traced run can say what the host was doing in each
gap of the device's work.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

clock = time.perf_counter


@dataclasses.dataclass
class Req:
    rid: int
    due: float  # host clock
    prompt: np.ndarray
    gen: int
    submit: Optional[float] = None
    admit: Optional[float] = None  # start of the step that admitted it
    stamps: List[float] = dataclasses.field(default_factory=list)
    tokens: Optional[List[int]] = None  # set when it finished
    truncated: bool = False


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    prompt_tokens: int  # prompt tokens consumed
    new_tokens: int  # tokens generated
    first_tokens: int  # streams that emitted their first token
    preemptions: int
    decode_only: bool  # every live stream had its first token already


def span(name: str):
    return jax.profiler.TraceAnnotation(name)


def warm_up(engine, conf: dict, traffic: dict, log) -> Dict[str, float]:
    """Compile and run each program this cell's traffic reaches, once, by
    serving one request through the public API: a ``chunk + 2`` token
    prompt (a chunk advance, then a chunk step that emits), then one-token
    steps; with oversubscription the stream is also parked and resumed
    (slice to the host pool, then the jitted slot write)."""
    from repro.launch.engine import Request

    k = conf["chunk"]
    prompt = np.arange(k + 2, dtype=np.int32) % conf["vocab_size"]
    engine.submit(Request(rid=-1, prompt=prompt, max_new_tokens=3))
    names = (["chunk advance + slot reset", "chunk step", "one-token step"]
             if k > 1 else ["slot reset + one-token step", "one-token step"])
    phases = {}
    for name in names:
        t = clock()
        engine.run(max_steps=1, keep_live=True)
        phases[name] = clock() - t
    if traffic["oversubscribe"] > 1:
        t = clock()
        engine.evict(-1, preserve=True)
        engine.resume(-1)
        phases["park to pool"] = clock() - t
        t = clock()
        engine.run(max_steps=1, keep_live=True)
        phases["resume (slot write) + one-token step"] = clock() - t
    t = clock()
    engine.run()
    phases["drain"] = clock() - t
    for name, s in phases.items():
        log(f"warm-up: {name} {s:.3f}s")
    return phases


class OpenLoop:
    """Feeds a schedule to the engine and records what the host saw."""

    def __init__(self, engine, arrivals, t0: float):
        self.engine = engine
        self.reqs = [Req(a.rid, t0 + a.due_s, a.prompt, a.gen)
                     for a in arrivals]
        self.by_rid = {r.rid: r for r in self.reqs}
        self.steps: List[Step] = []
        # host-clock span in which a profiler collected, if one did
        self.profiled: Optional[Tuple[float, float]] = None
        self._next = 0

    def done(self) -> bool:
        return all(r.tokens is not None for r in self.reqs)

    def serve(self, until: float) -> None:
        """Submit what is due and step the engine until ``until`` or until
        every request has finished."""
        from repro.launch.engine import Request

        eng = self.engine
        while not self.done():
            now = clock()
            while (self._next < len(self.reqs)
                   and self.reqs[self._next].due <= now):
                r = self.reqs[self._next]
                with span("harness.submit"):
                    eng.submit(Request(rid=r.rid, prompt=r.prompt,
                                       max_new_tokens=r.gen))
                r.submit = clock()
                self._next += 1
            if now >= until:
                return
            if not (eng.pending or eng.live):
                if self._next < len(self.reqs):
                    with span("harness.wait"):
                        time.sleep(max(0.0, min(
                            self.reqs[self._next].due, until) - clock()))
                continue
            before = eng.live_progress()
            n_log = len(eng.schedule_log)
            t0 = clock()
            with span("engine.step"):
                results, stats = eng.run(max_steps=1, keep_live=True)
            t1 = clock()
            with span("harness.stamp"):
                self._record(before, n_log, results, stats, t0, t1)

    def _record(self, before, n_log, results, stats, t0, t1) -> None:
        eng = self.engine
        admitted = False
        for _, event, rid, _ in eng.schedule_log[n_log:]:
            if event == "admit":
                admitted = True
                r = self.by_rid.get(rid)
                if r is not None and r.admit is None:
                    r.admit = t0
        counts = dict(eng.live_progress())
        for rid, res in results.items():
            counts[rid] = len(res.tokens)
        new = first = 0
        for rid, n in counts.items():
            r = self.by_rid.get(rid)
            if r is None:
                continue
            k = n - len(r.stamps)
            first += bool(k and not r.stamps)
            r.stamps.extend([t1] * k)
            new += k
            if rid in results:
                r.tokens = list(results[rid].tokens)
                r.truncated = results[rid].truncated
        self.steps.append(Step(
            t0=t0, t1=t1, prompt_tokens=stats.prompt_tokens, new_tokens=new,
            first_tokens=first,
            preemptions=stats.preemptions,
            decode_only=bool(before) and not admitted
            and all(n >= 1 for n in before.values())))
