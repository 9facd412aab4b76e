"""The split of the device's idle time by the engine's own spans
(``harness.phases``)."""
import os

import pytest

from harness import trace
from harness.trace import Event, Trace

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "lstm-rnnt.chat.xplane.pb.gz")
SPANS_FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                             "lstm-rnnt.chat.spans.xplane.pb.gz")


def recorded():
    """The trace of ``lstm-rnnt.chat`` recorded before the engine had spans
    of its own (the fixture of ``test_bench_trace.py``)."""
    return trace.load(FIXTURE)


def with_program_spans():
    """A window of 200 ns with the device busy at 0-20, 60-70 and 150-160,
    and the engine's spans of one ``run`` of two iterations under the
    harness's ``engine.step``: its idle gaps are 20-60, 70-150 and 160-200,
    the last 20 ns of them outside every span."""
    ops = [Event("kern", 0, 20, "jit_chunk_advance"),
           Event("kern", 60, 70, "jit_chunk_step"),
           Event("kern", 150, 160, "jit_step")]
    spans = [Event("engine.run", 0, 180),
             Event("engine.iteration", 5, 90),
             Event("engine.schedule", 10, 25), Event("engine.feed", 25, 40),
             Event("engine.dispatch", 40, 45), Event("engine.sync", 45, 80),
             Event("engine.commit", 80, 85),
             Event("engine.iteration", 90, 175),
             Event("engine.schedule", 95, 100),
             Event("engine.feed", 100, 110),
             Event("engine.dispatch", 110, 120),
             Event("engine.commit", 120, 170)]
    t = Trace(window=(0, 200), devices={"/device:TPU:0": ops},
              programs={"/device:TPU:0": []},
              host=[Event("engine.step", 0, 180)])
    return t, spans


def test_idle_under_nested_spans_goes_to_the_innermost_phase():
    from harness import phases

    t, spans = with_program_spans()
    got = phases.idle_by_phase(t, spans)
    # schedule 20-25, 95-100; feed 25-40, 100-110; dispatch 40-45,
    # 110-120; sync 45-60, 70-80; commit 80-85, 120-150, 160-170; loop
    # (iteration or run, no phase) 85-90, 90-95, 170-175, 175-180
    assert got == {"schedule": pytest.approx(10e-9),
                   "feed": pytest.approx(25e-9),
                   "dispatch": pytest.approx(15e-9),
                   "sync": pytest.approx(25e-9),
                   "commit": pytest.approx(45e-9),
                   "loop": pytest.approx(20e-9)}
    assert phases.iterations(t, spans) == 2
    assert phases.split(t, spans)["commit"] == pytest.approx(45e-9 * 1e3 / 2)


def test_phases_partition_the_idle_under_engine_run():
    """The five phases and ``loop`` split the idle under ``engine.run``
    whole: times the iteration count they add up to the harness's
    ``engine.step`` idle, which here covers the same time."""
    from harness import phases

    t, spans = with_program_spans()
    per = phases.split(t, spans)
    assert set(per) == set(phases.PHASES)
    step = dict(trace.idle_by_host(t))["engine.step"]
    assert sum(per.values()) * phases.iterations(t, spans) / 1e3 == \
        pytest.approx(step)
    assert step == pytest.approx(140e-9)
    # an iteration that starts outside the window is not counted
    late = spans + [Event("engine.iteration", 200, 210)]
    assert phases.iterations(t, late) == 2


def test_program_spans_leave_the_harness_reductions_alone():
    """The harness's own reductions read the same with the program's spans
    on the host plane as without: ``trace.load`` keeps only its own spans,
    and splitting by phase changes nothing in the trace."""
    from harness import phases

    t, spans = with_program_spans()
    before = (trace.idle_by_host(t), trace.top_ops(t), trace.busy_s(t))
    phases.idle_by_phase(t, spans)
    assert (trace.idle_by_host(t), trace.top_ops(t), trace.busy_s(t)) == \
        before
    r = recorded_spans()
    assert {h.name for h in r.host} <= set(trace.HOST_SPANS)
    assert {n for n, _ in trace.idle_by_host(r)} <= \
        set(trace.HOST_SPANS) | {"host.other"}


@pytest.mark.parametrize("phase", ["schedule", "feed", "dispatch", "sync",
                                   "commit", "loop"])
def test_step_idle_readers_read_nothing_without_program_spans(
        phase, tmp_path):
    """Each ``step_idle_ms.<phase>`` reader returns None without a trace,
    and with a trace whose program left no spans (the parent of this
    change, or a profile that is not the run's)."""
    from harness import metrics, phases

    read = metrics.reader(os.path.join(os.path.dirname(__file__), "..",
                                       "..", "bench", "metrics"),
                          f"step_idle_ms.{phase}")
    ctx = metrics.Context(conf={}, traffic={}, seconds=1.0, t0=0.0,
                          reqs=[], steps=[], setup_s=0.0)
    assert read(ctx) is None
    old = recorded()
    _, spans = phases.load_spans(FIXTURE)
    assert spans == []
    assert phases.split(old, spans) is None
    assert phases.spans_of(old, str(tmp_path)) == []


def recorded_spans():
    """A trace of ``lstm-rnnt.chat`` that carries the engine's own spans,
    recorded on one TPU v5 lite: a 0.25 s window of ``bench/run.py --trace
    1 --seconds 0.3`` (seed 3000014001), trimmed to the events that overlap
    the ``harness.window`` span or lie within 2 ms of it."""
    return trace.load(SPANS_FIXTURE)


# what the run printed from the untrimmed trace
SPANS_RUN = {"busy_s": 0.030753247, "window_s": 0.250944178,
             "device_idle_share": 87.74498486272911,
             "step_idle_ms.schedule": 0.06872215942028978,
             "step_idle_ms.feed": 0.5892986086956522,
             "step_idle_ms.dispatch": 1.1831652028983335,
             "step_idle_ms.sync": 1.1785532173913038,
             "step_idle_ms.commit": 0.012221463768115939,
             "step_idle_ms.loop": 0.13127915942028978,
             "engine.step": 0.21854825099998296}


def test_recorded_spans_reduce_to_what_the_chip_run_printed(tmp_path,
                                                            monkeypatch):
    """The reduction finds the engine's spans in the run's own profile and
    reproduces each ``step_idle_ms.<phase>`` the run printed; times the 69
    iterations they add up to the idle under ``engine.run``, within 0.2% of
    the breakdown's ``engine.step``."""
    import gzip
    import shutil

    from harness import metrics, phases

    t = recorded_spans()
    assert trace.busy_s(t) == pytest.approx(SPANS_RUN["busy_s"], rel=1e-9)
    assert trace.window_s(t) == pytest.approx(SPANS_RUN["window_s"],
                                              rel=1e-9)
    assert 100 * trace.idle_share(t) == pytest.approx(
        SPANS_RUN["device_idle_share"], rel=1e-9)
    step = dict(trace.idle_by_host(t))["engine.step"]
    assert step == pytest.approx(SPANS_RUN["engine.step"], rel=1e-9)
    # the reader finds the profile where the run wrote it
    prof = tmp_path / "plugins" / "profile" / "0"
    prof.mkdir(parents=True)
    with gzip.open(SPANS_FIXTURE, "rb") as f, \
            open(prof / "host.xplane.pb", "wb") as out:
        shutil.copyfileobj(f, out)
    spans = phases.spans_of(t, str(tmp_path))
    assert spans == phases.load_spans(SPANS_FIXTURE)[1]
    assert phases.iterations(t, spans) == 69
    got = phases.split(t, spans)
    for p, v in got.items():
        assert v == pytest.approx(SPANS_RUN[f"step_idle_ms.{p}"], rel=1e-9)
    assert sum(got.values()) * 69 / 1e3 == pytest.approx(step, rel=2e-3)
    # each reader reads the same, from the directory the run wrote to
    monkeypatch.setattr(phases, "TRACE_DIR", str(tmp_path))
    ctx = metrics.Context(conf={}, traffic={}, seconds=0.3, t0=0.0,
                          reqs=[], steps=[], setup_s=0.0, trace=t)
    bench_metrics = os.path.join(os.path.dirname(__file__), "..", "..",
                                 "bench", "metrics")
    for p in phases.PHASES:
        name = f"step_idle_ms.{p}"
        assert metrics.reader(bench_metrics, name)(ctx) == pytest.approx(
            SPANS_RUN[name], rel=1e-9)
