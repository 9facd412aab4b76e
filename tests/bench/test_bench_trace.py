"""The reduction from a profiler trace to device numbers."""
import os

import pytest

from harness import trace
from harness.trace import Event, Trace

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "lstm-rnnt.chat.xplane.pb.gz")


def synthetic():
    ops = [Event("a", 0, 10, "jit_step"), Event("kern", 5, 20, "jit_step"),
           Event("kern", 40, 50, "jit_chunk_step"),
           Event("b", 95, 120, "jit_step")]
    host = [Event("engine.step", 0, 30), Event("harness.wait", 30, 60),
            Event("harness.stamp", 60, 70)]
    return Trace(window=(0, 100), devices={"/device:TPU:0": ops},
                 programs={"/device:TPU:0": []}, host=host)


def test_busy_is_the_union_inside_the_window():
    t = synthetic()
    assert trace.busy_intervals(t.devices["/device:TPU:0"], t.window) == \
        [(0, 20), (40, 50), (95, 100)]
    assert trace.busy_s(t) == pytest.approx(35e-9)
    assert trace.window_s(t) == pytest.approx(100e-9)
    assert trace.idle_share(t) == pytest.approx(0.65)


def test_kernel_time_by_program_counts_whole_launches_in_the_window():
    t = synthetic()
    assert trace.kernel_time(t, r"^kern$") == {
        "jit_step": (pytest.approx(15e-9), 1),
        "jit_chunk_step": (pytest.approx(10e-9), 1)}


def test_op_names_drop_the_instruction_text():
    assert trace.op_name(
        "%quant_recurrent_seq_scan_pallas.13 = (s8[8,8,2048]{2,1,0}) "
        "custom-call(s32[8,8,6144]{2,1,0} %copy_bitcast_fusion.6)") == \
        "quant_recurrent_seq_scan_pallas"
    assert trace.op_name("%copy-start = (s8[2048,8192]) copy-start(x)") == \
        "copy-start"
    assert trace.op_name("fusion") == "fusion"


def test_idle_gaps_go_to_the_host_span_over_them():
    t = synthetic()
    got = dict(trace.idle_by_host(t))
    # gap 20-40: engine.step 10, wait 10; gap 50-95: wait 10, stamp 10,
    # nothing 25
    assert got == {"harness.wait": pytest.approx(20e-9),
                   "engine.step": pytest.approx(10e-9),
                   "harness.stamp": pytest.approx(10e-9),
                   "host.other": pytest.approx(25e-9)}
    assert trace.top_ops(t) == [("jit_step:kern", pytest.approx(15e-9)),
                                ("jit_step:a", pytest.approx(10e-9)),
                                ("jit_chunk_step:kern", pytest.approx(10e-9)),
                                ("jit_step:b", pytest.approx(5e-9))]


def recorded():
    """A trace of ``lstm-rnnt.chat`` recorded on one TPU v5 lite: a 0.1 s
    window of ``bench/run.py --trace 1`` (seed 341), trimmed to the events
    within 2 ms of the ``harness.window`` span.  The run printed busy_s
    0.018191784, window_s 0.100427754, device_idle_share
    81.88570064008401, scan_roofline.decode 43.34958014675863 and
    scan_roofline.prefill 6.742912098646539 from the untrimmed trace."""
    return trace.load(FIXTURE)


def test_recorded_trace_reduces_to_what_the_chip_run_printed():
    t = recorded()
    assert list(t.devices) == ["/device:TPU:0"]
    assert trace.window_s(t) == pytest.approx(0.100427754, rel=1e-9)
    assert trace.busy_s(t) == pytest.approx(0.018191784, rel=1e-9)
    assert 100 * trace.idle_share(t) == pytest.approx(81.88570064008401,
                                                      rel=1e-9)
    idle = sum(s for _, s in trace.idle_by_host(t))
    assert idle == pytest.approx(trace.window_s(t) - trace.busy_s(t))


def test_recorded_trace_finds_the_scan_kernel_in_each_program():
    from harness import kernels, peaks
    from harness.metrics import Context

    t = recorded()
    per = trace.kernel_time(t, kernels.SCAN_KERNEL)
    assert set(per) == {"jit_step", "jit_chunk_step", "jit_chunk_advance"}
    assert all(n > 0 and s > 0 for s, n in per.values())
    with open(os.path.join(os.path.dirname(__file__), "..", "..", "bench",
                           "configs", "lstm-rnnt.json")) as f:
        import json

        conf = json.load(f)
    ctx = Context(conf=conf, traffic={}, seconds=0.3, t0=0.0, reqs=[],
                  steps=[], setup_s=0.0, peaks=peaks.peaks("TPU v5 lite"),
                  trace=t)
    assert kernels.scan_roofline(ctx, kernels.DECODE_PROGRAMS, 1) == \
        pytest.approx(43.34958014675863, rel=1e-9)
    assert kernels.scan_roofline(ctx, kernels.PREFILL_PROGRAMS, 8) == \
        pytest.approx(6.742912098646539, rel=1e-9)


def test_program_time_counts_whole_executions_in_the_window():
    t = Trace(window=(0, 100), devices={"/device:TPU:0": []},
              programs={"/device:TPU:0": [Event("jit_step", 0, 10),
                                          Event("jit_step", 20, 30),
                                          Event("jit_chunk_step", 40, 60),
                                          Event("jit_step", 95, 105)]},
              host=[])
    assert trace.program_time(t, r"^jit_") == {
        "jit_step": (pytest.approx(20e-9), 2),
        "jit_chunk_step": (pytest.approx(20e-9), 1)}


@pytest.mark.parametrize("programs,n_execs", [
    ("DECODE_PROGRAMS", {"jit_step": 24}),
    ("PREFILL_PROGRAMS", {"jit_chunk_advance": 5, "jit_chunk_step": 1})])
def test_recorded_trace_step_mfu_is_ops_at_peak_over_device_time(
        programs, n_execs):
    """The recorded one-token executions take ~0.32 ms of device time for
    8 rows x 259 MOP int8 (5.3 us at 393 TOP/s) plus a 42 MFLOP bf16 head
    (0.2 us at 197 TFLOP/s): ~1.7% of the peak."""
    import json

    from harness import kernels, peaks
    from harness.metrics import Context

    t = recorded()
    with open(os.path.join(os.path.dirname(__file__), "..", "..", "bench",
                           "configs", "lstm-rnnt.json")) as f:
        conf = json.load(f)
    pk = peaks.peaks("TPU v5 lite")
    per = trace.program_time(t, getattr(kernels, programs))
    assert {k: n for k, (_, n) in per.items()} == n_execs
    least = 0.0
    for prog, (_, n) in per.items():
        rows = 8 * (1 if prog == "jit_step" else 8)
        head = 0 if prog == "jit_chunk_advance" else 8 * 2 * 640 * 4096
        least += n * (rows * 2 * 129_499_136 / 393e12 + head / 197e12)
    want = 100 * least / sum(s for s, _ in per.values())
    ctx = Context(conf=conf, traffic={}, seconds=0.3, t0=0.0, reqs=[],
                  steps=[], setup_s=0.0, peaks=pk, trace=t)
    got = kernels.step_mfu(ctx, getattr(kernels, programs))
    assert got == pytest.approx(want, rel=1e-12)
    assert 0 < got < 100
    if programs == "DECODE_PROGRAMS":
        assert 1.5 < got < 1.9
