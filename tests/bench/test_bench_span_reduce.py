"""The reduction of the program's own marks in a trace (``bench/span_reduce.py``):
device time per named scope and step, the host's sync latency, and idle time
split by the innermost program span."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import span_reduce  # noqa: E402

MS = 1_000_000  # ns


def planes():
    """Two steps (0-4 ms, 6-9 ms) and an admission (10-11 ms) on one device;
    the host's spans around them."""
    ops = [("bpd.trunk", 0 * MS, 2 * MS), ("bpd.heads", 2 * MS, 3 * MS),
           ("bpd.verify", 3 * MS, 4 * MS),
           ("no scope", 6 * MS, 9 * MS),        # a while loop: its body
           ("bpd.trunk", 6 * MS, 8 * MS),       # ops lie inside it
           ("bpd.heads", 8 * MS, 9 * MS),
           ("bpd.prefill", 10 * MS, 11 * MS)]
    mods = [("step_windowed", 0 * MS, 4 * MS),
            ("step_windowed", 6 * MS, 9 * MS),
            ("admit", 10 * MS, 11 * MS)]
    spans = [("bench.window", 0 * MS, 12 * MS),
             ("serve.frontend.tick", 0 * MS, 5.5 * MS),
             ("serve.engine.sync", 1 * MS, 4.5 * MS),
             ("serve.engine.harvest", 4.5 * MS, 5 * MS),
             ("serve.frontend.tick", 5.8 * MS, 12 * MS),
             ("serve.engine.dispatch", 5.8 * MS, 6.1 * MS),
             ("serve.engine.sync", 6.1 * MS, 9.5 * MS),
             ("serve.engine.admit", 9.5 * MS, 10.2 * MS)]
    return [{"modules": mods, "ops": ops}], spans


def test_scope_seconds_per_step():
    devices, _ = planes()
    red = span_reduce.scope_seconds(devices, 0, 12 * MS)
    assert red["steps"] == 2
    assert red["step_s"] == pytest.approx(0.007)
    assert red["scope_s"]["bpd.trunk"] == pytest.approx(0.004)
    assert red["scope_s"]["bpd.heads"] == pytest.approx(0.002)
    assert red["scope_s"]["bpd.verify"] == pytest.approx(0.001)
    # the container is left out; the admission is no step's
    assert "no scope" not in red["scope_s"]
    assert "bpd.prefill" not in red["scope_s"]
    # a step cut by the window's edge is left out whole
    cut = span_reduce.scope_seconds(devices, 1 * MS, 12 * MS)
    assert cut["steps"] == 1
    assert cut["scope_s"]["bpd.trunk"] == pytest.approx(0.002)


def test_sync_latency_per_step():
    devices, spans = planes()
    lat = span_reduce.sync_seconds(devices, spans, 0, 12 * MS)
    assert lat == [pytest.approx(0.0005), pytest.approx(0.0005)]


def test_idle_split_by_innermost_span():
    devices, spans = planes()
    split = dict(span_reduce.idle_split(devices, spans, 0, 12 * MS))
    # idle: 4-6, 9-10 and 11-12 ms
    assert split == {
        "serve.engine.sync": pytest.approx(0.001),       # 4-4.5, 9-9.5
        "serve.engine.harvest": pytest.approx(0.0005),   # 4.5-5
        "serve.frontend.tick": pytest.approx(0.0015),    # 5-5.5, 11-12
        span_reduce.NO_SPAN: pytest.approx(0.0003),      # 5.5-5.8
        "serve.engine.dispatch": pytest.approx(0.0002),  # 5.8-6
        "serve.engine.admit": pytest.approx(0.0005)}     # 9.5-10
    assert sum(split.values()) == pytest.approx(0.004)


def test_names_and_scopes():
    assert span_reduce.span_name("serve.engine.admit#rid=7#") == \
        "serve.engine.admit"
    assert span_reduce.scope_of(
        "jit(step_windowed)/while/body/bpd.heads/dot_general:") == \
        "bpd.heads"
    assert span_reduce.scope_of("jit(step_windowed)/add:") == \
        span_reduce.NO_SCOPE


CHIP_TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "chip_trace_spans.xplane.pb.gz")


def test_reduction_of_a_recorded_chip_trace_with_program_marks():
    """0.3 s of a traced window of granite-3-8b.l16.decode-backlog on one
    TPU v5e ("TPU v5 lite"), recorded with the program's spans and each
    op's metadata: the named scopes cover the step, the host sees a step's
    end within milliseconds, and the split of the idle time adds up to
    what the benchmark's own reduction finds idle."""
    from bench import trace_reduce

    red = span_reduce.reduce_path(CHIP_TRACE)
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(0.3)
    assert red["steps"] >= 6
    scoped = sum(s for k, s in red["scope_s"].items()
                 if k.startswith(span_reduce.SCOPE_PREFIX))
    assert scoped >= 0.9 * red["step_s"]
    assert (red["scope_s"]["bpd.heads"] + red["scope_s"]["bpd.trunk"]
            <= red["step_s"])
    sync_ms = sorted(1e3 * s for s in red["sync_s"])
    assert len(sync_ms) >= red["steps"]
    assert 0.5 <= sync_ms[len(sync_ms) // 2] <= 3.0

    devices, spans = trace_reduce.read_planes(CHIP_TRACE)
    old = trace_reduce.reduce_planes(devices, spans)
    idle = old["window_s"] - old["busy_s"]
    # ProfileData rounds each event to whole ns; this reader keeps the ps
    assert sum(s for _, s in red["idle_split"]) == pytest.approx(idle,
                                                                 rel=1e-3)
    assert "serve.engine.sync" in dict(red["idle_split"])
    # the benchmark's own attribution still reads its own spans
    assert {n for n, _ in old["idle_by_span"]} <= (
        {n for n, _, _ in spans if n.startswith("bench.")}
        | {trace_reduce.NO_SPAN})
