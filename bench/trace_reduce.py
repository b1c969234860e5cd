"""Reduce a ``jax.profiler`` trace to the numbers the per-layer metrics read.

Reads the ``.xplane.pb`` with ``jax.profiler.ProfileData`` and nothing
else.  Device planes are the ``/device:TPU:<n>`` planes; on each, the
"XLA Ops" line holds one event per device operation and the "XLA Modules"
line one event per execution of a compiled program, named after the jitted
function (``jit_step_windowed(...)``, ``jit_admit(...)``).  Host spans are
the benchmark's own ``TraceAnnotation``s, named ``bench.*``, on the host
plane's thread lines; host and device events share the trace's clock.

Within the traced window (the ``bench.window`` span, else the whole trace):

  busy_s        union of the device operations' intervals (mean over chips)
  programs      {program: {"count", "seconds", "collective_s"}} device time
                per program, and the time of the collective operations
                (all-reduce, all-gather, ...) inside it
  top_ops       [[op, seconds]] the operations that took most time
  idle_by_span  [[host span, seconds]] device idle time, by the innermost
                benchmark span open on the host at the middle of each gap
"""
from __future__ import annotations

import bisect
import glob
import gzip
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
NO_SPAN = "no benchmark span"

Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def program_name(module_event: str) -> str:
    """``jit_step_windowed(123)`` -> ``step_windowed``."""
    m = re.match(r"(?:jit_)?([A-Za-z0-9_.]+?)(?:\(|$|\.\d+$)", module_event)
    return m.group(1) if m else module_event


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def read_planes(path: str):
    """(device planes as {line name: events}, host spans) of one trace
    (``.xplane.pb``, or the same gzipped)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path) as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            devices.append({line.name: _events(line) for line in plane.lines})
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                spans += [ev for ev in _events(line)
                          if ev[0].startswith(SPAN_PREFIX)]
    return devices, spans


def reduce_planes(devices: List[Dict], spans: List) -> Dict:
    windows = [(a, b) for name, a, b in spans if name == WINDOW_SPAN]
    if windows:
        lo, hi = windows[0]
    else:
        every = [t for d in devices for evs in d.values()
                 for _, a, b in evs for t in (a, b)]
        lo, hi = (min(every), max(every)) if every else (0.0, 0.0)
    window_ns = hi - lo
    inner = sorted(((a, b, n) for n, a, b in spans if n != WINDOW_SPAN))
    starts = [a for a, _, _ in inner]
    longest = max((b - a for a, b, _ in inner), default=0.0)

    def span_at(t: float) -> str:
        """Innermost benchmark span open at ``t`` on any host thread."""
        best = None
        i = bisect.bisect_right(starts, t) - 1
        while i >= 0 and starts[i] >= t - longest:
            a, b, n = inner[i]
            if t < b and (best is None or b - a < best[0]):
                best = (b - a, n)
            i -= 1
        return best[1] if best else NO_SPAN

    programs: Dict[str, Dict[str, float]] = defaultdict(
        lambda: {"count": 0, "seconds": 0.0, "collective_s": 0.0})
    ops: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    busy_ns = 0.0
    for dev in devices:
        op_events = dev.get("XLA Ops") or dev.get("XLA Modules") or []
        modules = sorted((a, b, program_name(name))
                         for name, a, b in dev.get("XLA Modules", []))
        mod_starts = [a for a, _, _ in modules]
        for a, b, name in modules:
            if lo <= (a + b) / 2 < hi:
                programs[name]["count"] += 1
                programs[name]["seconds"] += (b - a) * 1e-9
        clipped = []
        for name, a, b in op_events:
            for a2, b2 in _clip([(a, b)], lo, hi):
                clipped.append((a2, b2))
                ops[name] += (b2 - a2) * 1e-9
                if any(c in name for c in COLLECTIVES):
                    i = bisect.bisect_right(mod_starts, a) - 1
                    if i >= 0 and a < modules[i][1]:
                        programs[modules[i][2]]["collective_s"] += \
                            (b2 - a2) * 1e-9
        busy = union(clipped)
        busy_ns += sum(b - a for a, b in busy)
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                idle[span_at((a + b) / 2)] += (b - a) * 1e-9
    n = max(len(devices), 1)
    return {
        "devices": len(devices),
        "window_s": window_ns * 1e-9,
        "busy_s": busy_ns * 1e-9 / n,
        "programs": {k: {key: x / n for key, x in v.items()}
                     for k, v in programs.items()},
        "top_ops": sorted(([k, v / n] for k, v in ops.items()),
                          key=lambda kv: -kv[1])[:10],
        "idle_by_span": sorted(([k, v / n] for k, v in idle.items()),
                               key=lambda kv: -kv[1])[:10],
    }


STEP_PROGRAM = "step_windowed"


class TraceError(RuntimeError):
    """A chip trace in which the reduction finds no device plane or no
    execution of the step program."""


def require_step(reduced: Optional[Dict]) -> None:
    """Refuse a chip trace that the per-layer metrics could not read: a
    renamed plane, line or program would otherwise drop them silently."""
    if reduced is None:
        raise TraceError("the trace holds no /device:TPU plane")
    if not reduced["programs"].get(STEP_PROGRAM, {}).get("count"):
        raise TraceError(f"no execution of {STEP_PROGRAM} in the traced "
                         f"window (programs: {sorted(reduced['programs'])})")


def reduce_trace(trace_dir: str) -> Optional[Dict]:
    """The reduction of the newest trace under ``trace_dir``; None when
    there is none or it holds no device plane."""
    path = find_xplane(trace_dir)
    if path is None:
        return None
    devices, spans = read_planes(path)
    if not devices:
        return None
    return reduce_planes(devices, spans)
