"""The trace reduction: busy and idle time, device time per program, and
idle gaps attributed to the host span open at the time."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import trace_reduce  # noqa: E402

MS = 1_000_000  # ns


def test_reduce_planes_busy_idle_programs():
    ops = [("fusion.1", 0 * MS, 3 * MS), ("fusion.2", 2 * MS, 4 * MS),
           ("all-reduce.3", 6 * MS, 7 * MS), ("fusion.1", 8 * MS, 9 * MS)]
    mods = [("jit_step_windowed(17)", 0 * MS, 4 * MS),
            ("jit_step_windowed(17)", 6 * MS, 7 * MS),
            ("jit_admit(3)", 8 * MS, 9 * MS)]
    device = {"XLA Ops": ops, "XLA Modules": mods}
    spans = [("bench.window", 0 * MS, 10 * MS),
             ("bench.frontend.tick", 3 * MS, 10 * MS),
             ("bench.engine.poll_progress", 4 * MS, 6 * MS)]
    red = trace_reduce.reduce_planes([device, device], spans)
    assert red["devices"] == 2
    assert red["window_s"] == pytest.approx(0.010)
    assert red["busy_s"] == pytest.approx(0.006)     # 0-4, 6-7, 8-9 ms
    assert red["programs"]["step_windowed"] == {
        "count": 2, "seconds": pytest.approx(0.005),
        "collective_s": pytest.approx(0.001)}
    assert red["programs"]["admit"]["collective_s"] == 0
    assert red["programs"]["admit"]["count"] == 1
    assert red["top_ops"][0] == ["fusion.1", pytest.approx(0.004)]
    idle = dict(red["idle_by_span"])
    assert idle["bench.engine.poll_progress"] == pytest.approx(0.002)
    assert idle["bench.frontend.tick"] == pytest.approx(0.002)  # 7-8, 9-10


def test_window_clips_and_no_window_spans_everything():
    device = {"XLA Ops": [("fusion.1", 0, 4 * MS), ("fusion.2", 8 * MS,
                                                    12 * MS)]}
    clipped = trace_reduce.reduce_planes(
        [device], [("bench.window", 2 * MS, 10 * MS)])
    assert clipped["busy_s"] == pytest.approx(0.004)
    assert dict(clipped["idle_by_span"])[trace_reduce.NO_SPAN] == \
        pytest.approx(0.004)
    whole = trace_reduce.reduce_planes([device], [])
    assert whole["window_s"] == pytest.approx(0.012)
    assert whole["busy_s"] == pytest.approx(0.008)


def test_program_names():
    assert trace_reduce.program_name("jit_step_windowed(123)") == \
        "step_windowed"
    assert trace_reduce.program_name("jit_admit") == "admit"


CHIP_TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "chip_trace.xplane.pb.gz")


def _covered(intervals):
    """Length covered by intervals, by a sweep over their edges."""
    edges = sorted([(a, 1) for a, _ in intervals]
                   + [(b, -1) for _, b in intervals])
    depth = total = 0
    last = None
    for t, step in edges:
        if depth > 0:
            total += t - last
        depth += step
        last = t
    return total


def test_reduction_of_a_recorded_chip_trace():
    """0.3 s of a traced window of granite-3-8b.l16.decode-backlog on one
    TPU v5e ("TPU v5 lite"), cut to the device plane and the benchmark's
    spans: the reduction finds the planes and lines it reads, and its
    numbers agree with a plain count over the same events."""
    devices, spans = trace_reduce.read_planes(CHIP_TRACE)
    assert len(devices) == 1
    dev = devices[0]
    red = trace_reduce.reduce_planes(devices, spans)
    trace_reduce.require_step(red)
    lo, hi = next((a, b) for n, a, b in spans
                  if n == trace_reduce.WINDOW_SPAN)
    assert red["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert red["window_s"] == pytest.approx(0.3)
    ops = [(max(a, lo), min(b, hi)) for _, a, b in dev["XLA Ops"]
           if b > lo and a < hi]
    assert red["busy_s"] == pytest.approx(_covered(ops) * 1e-9)
    assert red["busy_s"] == pytest.approx(0.2596389, rel=1e-6)
    steps = [b - a for n, a, b in dev["XLA Modules"]
             if trace_reduce.program_name(n) == trace_reduce.STEP_PROGRAM
             and lo <= (a + b) / 2 < hi]
    prog = red["programs"][trace_reduce.STEP_PROGRAM]
    assert prog["count"] == len(steps) == 8
    assert prog["seconds"] == pytest.approx(sum(steps) * 1e-9)
    assert prog["seconds"] / prog["count"] == pytest.approx(0.02623, rel=1e-3)
    assert red["programs"]["admit"]["count"] == 3
    idle = sum(s for _, s in red["idle_by_span"])
    assert idle == pytest.approx(red["window_s"] - red["busy_s"])
    assert {n for n, _ in red["idle_by_span"]} <= (
        {n for n, _, _ in spans} | {trace_reduce.NO_SPAN})
    assert "bench.engine.poll_progress" in dict(red["idle_by_span"])
