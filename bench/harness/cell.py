"""One run of one cell: build, warm up, serve the open-loop window, drain,
read the metrics, check the served tokens against the reference, print.

Everything a cell is made of is found by name: ``BENCHMARK.json`` names
the cell's configuration and traffic; ``configs/<config>.json``,
``traffic/<traffic>.json`` and ``limits/<cell>.json`` hold their data, and
``metrics/<metric>.py`` reads each metric.
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import sys
from typing import Callable, List, Optional

import jax
import numpy as np

from harness import check, loop, metrics, model, peaks, trace, traffic

DRAIN_S = 60.0  # how long past the window's close a due request may take
# a --trace 1 run traces TRACE_S seconds from TRACE_AT_S into the window (a
# TPU trace holds ~35 MB a second of this serving loop); the profiler
# collects for TRACE_S + TRACE_PAD_S seconds from its start
TRACE_S = 3.0
TRACE_AT_S = 10.0
TRACE_PAD_S = 1.0
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


@dataclasses.dataclass
class Paths:
    spec: str  # BENCHMARK.json
    data: str  # holds configs/, traffic/, limits/
    metrics: str  # holds <metric>.py readers
    trace_dir: str  # where a --trace 1 run writes its profile


def require_chips(n: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU found (JAX platform is "
                         f"{devices[0].platform!r}); the benchmark runs "
                         f"only on a TPU")
    if len(devices) < n:
        raise SystemExit(f"bench: the cell needs {n} TPU chips, found "
                         f"{len(devices)}")
    return devices[:n]


class CompileCounter:
    """Counts traces and compiles while ``active``."""

    def __init__(self):
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event in COMPILE_EVENTS:
            self.count += 1


def enable_cache() -> None:
    """JAX's persistent compilation cache, where the program keeps it (a
    fixed directory in the checkout unless ``JAX_COMPILATION_CACHE_DIR``
    says otherwise), for every program however quick to compile: after a
    cell's first run, set-up finds all of them there."""
    from repro.launch import serve

    serve.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(spec: dict, data: str, workload: str):
    """``(cell, configuration, traffic, limits)`` of a workload named in
    ``BENCHMARK.json``, each read from its own file under ``data``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: unknown workload {workload!r}; have "
                         f"{sorted(cells)}")
    cell = cells[workload]

    def find(kind, name):
        return load_json(os.path.join(data, kind, f"{name}.json"))

    return (cell, find("configs", cell["config"]),
            find("traffic", cell["traffic"]), find("limits", workload))


def _device_info(devices, trace_info=None) -> dict:
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": peak}
    if trace_info:
        out.update(trace_info)
    return out


def _cell_metrics(spec: dict, kind: str, cell: str) -> List[dict]:
    return [m for m in spec[kind]
            if "workloads" not in m or cell in m["workloads"]]


def build(conf: dict, mix: dict, backend: str, log):
    """Seeded weights, calibration and quantization, the engine, and its
    warm-up; returns ``(float params, engine)``."""
    cfg = model.arch_config(conf)
    t = loop.clock()
    params, calib = model.make_weights(cfg, conf)
    jax.block_until_ready((params, calib))
    log(f"setup: seeded weights on the device {loop.clock() - t:.3f}s")
    t = loop.clock()
    qlayers = model.quantize(params, cfg, calib)
    jax.block_until_ready(qlayers)
    log(f"setup: calibration + quantization {loop.clock() - t:.3f}s")
    engine = model.engine(params, qlayers, cfg, conf, mix, backend)
    loop.warm_up(engine, conf, mix, log)
    return params, engine


def schedule(mix: dict, seconds: float, seed: int, conf: dict):
    arrivals = traffic.schedule(mix, seconds, seed, conf["vocab_size"])
    length = mix["check"]["length"]
    for a in arrivals:
        if a.prompt.size + a.gen - 1 > length:
            raise ValueError(f"traffic {mix['name']}: a request of "
                             f"{a.prompt.size}+{a.gen} tokens exceeds the "
                             f"check's block length {length}")
    return arrivals


def trace_at(seconds: float) -> float:
    """Seconds into a window of ``seconds`` at which its trace starts:
    ``TRACE_AT_S``, or earlier in a window too short to hold it."""
    return min(TRACE_AT_S, max(0.0, seconds - TRACE_S) / 2)


def serve_window(engine, arrivals, t0: float, seconds: float,
                 counter: Optional[CompileCounter] = None,
                 profile: Optional[Callable[[], None]] = None
                 ) -> loop.OpenLoop:
    """Serve the schedule from ``t0`` for ``seconds``, then drain every
    request due in the window (at most ``DRAIN_S`` past its close).  With
    ``profile``, it is called ``trace_at(seconds)`` into the window to
    start the profiler; the ``harness.window`` span then covers the next
    ``TRACE_S`` seconds, ``feed.profiled`` the seconds the profiler
    collects, and the profiler is stopped after the drain."""
    feed = loop.OpenLoop(engine, arrivals, t0)
    if counter is not None:
        counter.active = True
    if profile is not None:
        feed.serve(until=t0 + trace_at(seconds))
        start = loop.clock()
        profile()
        feed.profiled = (start, loop.clock() + TRACE_S + TRACE_PAD_S)
        with loop.span(trace.WINDOW_SPAN):
            feed.serve(until=min(loop.clock() + TRACE_S, t0 + seconds))
    feed.serve(until=t0 + seconds)
    if counter is not None:
        counter.active = False
    feed.serve(until=t0 + seconds + DRAIN_S)
    if profile is not None:
        jax.profiler.stop_trace()
    return feed


def served_gap(params, reqs, conf: dict, mix: dict, seed: int, log) -> dict:
    """Gap numbers of a sample of the finished requests against the
    reference."""
    samples = check.sample(reqs, seed, mix["check"]["requests"])
    t = loop.clock()
    numbers, differ = check.served_gap(
        check.Reference(conf), params, samples, mix["check"]["requests"],
        mix["check"]["length"])
    log(f"check: {len(samples)} requests, {numbers['compared']} served "
        f"tokens against the reference, {differ} differ from its argmax, "
        f"max gap {numbers['max_logit_gap']!r}, mean gap "
        f"{numbers['mean_logit_gap']!r} ({loop.clock() - t:.1f}s)")
    return numbers


def run(paths: Paths, workload: str, seed: int, seconds: float,
        traced: bool, t_proc: float, *, require_chip: bool = True,
        backend: str = "pallas",
        log: Callable[[str], None] = print) -> dict:
    spec = load_json(paths.spec)
    cell, conf, mix, limits = load_cell(spec, paths.data, workload)
    devices = (require_chips(cell["chips"]) if require_chip
               else jax.devices()[:cell["chips"]])
    chip_peaks = peaks.peaks(devices[0].device_kind) if require_chip \
        else None
    log(f"device: {devices[0].platform} {devices[0].device_kind} x "
        f"{len(devices)}; jax {jax.__version__}")

    params, engine = build(conf, mix, backend, log)
    arrivals = schedule(mix, seconds, seed, conf)
    counter = CompileCounter()
    profile = None
    if traced:
        shutil.rmtree(paths.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.duration_ms = int((TRACE_S + TRACE_PAD_S) * 1e3)

        def profile():
            jax.profiler.start_trace(paths.trace_dir, profiler_options=opts)
    t0 = loop.clock()
    setup_s = t0 - t_proc
    feed = serve_window(engine, arrivals, t0, seconds, counter, profile)
    in_window = [st for st in feed.steps if st.t1 <= t0 + seconds]
    log(f"window: {len(arrivals)} requests due in {seconds:g}s, "
        f"{len(in_window)} engine steps in it "
        f"({sum(st.decode_only for st in in_window)} decode-only); "
        f"compilations inside the window: {counter.count}")
    late = [r.submit - r.due for r in feed.reqs if r.submit is not None]
    if late:
        log(f"generator lateness: p50 {np.median(late) * 1e3:.3f} ms, max "
            f"{max(late) * 1e3:.3f} ms")

    trace_info = tr = None
    if traced:
        t = loop.clock()
        tr = trace.load(trace.find(paths.trace_dir))
        log(f"trace: {sum(map(len, tr.devices.values()))} device ops in "
            f"{trace.window_s(tr):.3f}s, read in {loop.clock() - t:.1f}s")
        trace_info = {"busy_s": trace.busy_s(tr),
                      "window_s": trace.window_s(tr)}
    device = _device_info(devices, trace_info)
    ctx = metrics.Context(conf=conf, traffic=mix, seconds=seconds, t0=t0,
                          reqs=feed.reqs, steps=feed.steps,
                          setup_s=setup_s, peaks=chip_peaks, trace=tr,
                          profiled=feed.profiled)
    kind = "per_layer" if traced else "end_to_end"
    values = metrics.read_all(paths.metrics,
                              _cell_metrics(spec, kind, workload), ctx)
    for name, v in values.items():
        log(f"metric: {name} {v['value']!r} {v['unit']}")
    if not traced:
        # the host's per-layer readings of an untraced run, to set beside
        # those of a traced one, and the end-to-end readings the cell does
        # not report
        host = [m for m in _cell_metrics(spec, "per_layer", workload)
                if m["source"] != "device_trace"]
        host += [m for m in spec["end_to_end"]
                 if workload not in m.get("workloads", [workload])]
        for name, v in metrics.read_all(paths.metrics, host, ctx).items():
            log(f"unreported: {name} {v['value']!r} {v['unit']}")

    failed = sum(1 for r in feed.reqs
                 if r.tokens is None or r.truncated or len(r.tokens) != r.gen)
    del engine
    gaps = served_gap(params, feed.reqs, conf, mix, seed, log)
    numbers = [(name, gaps[name], lim["limit"])
               for name, lim in limits["compare"].items()]
    numbers.append(("unfinished", float(failed), 0.0))
    correct = check.verdict(numbers) and gaps["compared"] > 0
    result = {"correct": bool(correct), "attempted": len(feed.reqs),
              "failed": failed, "metrics": values, "device": device}
    if traced:
        result["breakdown"] = {"device_ops": trace.top_ops(tr),
                               "idle_gaps": trace.idle_by_host(tr)}
    result["check"] = {name: {"value": v, "limit": lim}
                       for name, v, lim in numbers}
    sys.stdout.flush()
    for name, v, lim in numbers:
        print(f"check: {name} {v!r} limit {lim!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return result
