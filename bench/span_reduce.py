"""Read what the serving program marks in a ``jax.profiler`` trace.

``bench/trace_reduce.py`` reduces the device planes and the benchmark's own
``bench.*`` spans; this module reads, from the same trace, what the program
marks itself:

  program spans  the ``serve.*`` annotations of ``repro.serving.tracing`` on
                 the host plane (any ``#key=value#`` suffix cut off);
  scopes         the ``bpd.*`` named scopes of each device operation, from
                 the operation's ``tf_op`` stat (the HLO ``op_name``); a
                 fusion carries its root's.

It reduces them, within the traced window (``bench.window``), to:

  scope_s       {scope: device seconds} summed over the executions of the
                step program that lie wholly in the window, with their
                count and device seconds (``steps``, ``step_s``);
  sync_s        per step execution, the end of the next
                ``serve.engine.sync`` minus the execution's end: how long
                the host takes to see that the device is done;
  idle_split    [[span, seconds]] the device's idle time, each instant
                charged to the innermost program span open on the host,
                ``no program span`` where none is;
  span_max_s    {span: seconds} the longest instance of each program span.

``jax.profiler.ProfileData`` does not expose the per-operation stats, so
the trace is read as the XSpace protobuf, against the few messages it needs
(declared below, wire-compatible with ``xplane.proto``).
"""
from __future__ import annotations

import bisect
import functools
import gzip
import os
import statistics
import time
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from bench.trace_reduce import (STEP_PROGRAM, WINDOW_SPAN, find_xplane,
                                program_name, union)

SPAN_PREFIX = "serve."
SCOPE_PREFIX = "bpd."
NO_SPAN = "no program span"
NO_SCOPE = "no scope"
SYNC_SPAN = "serve.engine.sync"
TRACE_ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".bench_trace")


@functools.lru_cache(maxsize=1)
def _xspace_class():
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")

    def message(name, fields):
        m = fd.message_type.add(name=name)
        for fname, number, kind, ref in fields:
            f = m.field.add(name=fname, number=number, type=kind,
                            label=F.LABEL_REPEATED if ref and ref[0] == "*"
                            else F.LABEL_OPTIONAL)
            if ref:
                f.type_name = ".bench_xplane." + ref.lstrip("*")

    I64, STR = F.TYPE_INT64, F.TYPE_STRING
    message("XStat", [("metadata_id", 1, I64, None),
                      ("double_value", 2, F.TYPE_DOUBLE, None),
                      ("uint64_value", 3, F.TYPE_UINT64, None),
                      ("int64_value", 4, I64, None),
                      ("str_value", 5, STR, None),
                      ("bytes_value", 6, F.TYPE_BYTES, None),
                      ("ref_value", 7, F.TYPE_UINT64, None)])
    message("XEvent", [("metadata_id", 1, I64, None),
                       ("offset_ps", 2, I64, None),
                       ("duration_ps", 3, I64, None),
                       ("stats", 4, F.TYPE_MESSAGE, "*XStat")])
    message("XLine", [("id", 1, I64, None), ("name", 2, STR, None),
                      ("timestamp_ns", 3, I64, None),
                      ("events", 4, F.TYPE_MESSAGE, "*XEvent")])
    message("XEventMetadata", [("id", 1, I64, None),
                               ("name", 2, STR, None),
                               ("stats", 5, F.TYPE_MESSAGE, "*XStat")])
    message("XStatMetadata", [("id", 1, I64, None), ("name", 2, STR, None)])
    # map<int64, ...> fields travel as repeated {key = 1, value = 2}
    message("EventMetadataEntry", [("key", 1, I64, None),
                                   ("value", 2, F.TYPE_MESSAGE,
                                    "XEventMetadata")])
    message("StatMetadataEntry", [("key", 1, I64, None),
                                  ("value", 2, F.TYPE_MESSAGE,
                                   "XStatMetadata")])
    message("XPlane", [("id", 1, I64, None), ("name", 2, STR, None),
                       ("lines", 3, F.TYPE_MESSAGE, "*XLine"),
                       ("event_metadata", 4, F.TYPE_MESSAGE,
                        "*EventMetadataEntry"),
                       ("stat_metadata", 5, F.TYPE_MESSAGE,
                        "*StatMetadataEntry"),
                       ("stats", 6, F.TYPE_MESSAGE, "*XStat")])
    message("XSpace", [("planes", 1, F.TYPE_MESSAGE, "*XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def parse_xspace(data: bytes):
    """An XSpace message of the fields declared above."""
    space = _xspace_class()()
    space.ParseFromString(data)
    return space


def span_name(name: str) -> str:
    """``serve.engine.admit#rid=7#`` -> ``serve.engine.admit``."""
    return name.split("#", 1)[0]


def scope_of(op_name: str) -> str:
    """The ``bpd.*`` component of an ``op_name``, else ``no scope``."""
    for part in op_name.split("/"):
        if part.startswith(SCOPE_PREFIX):
            return part.rstrip(":")
    return NO_SCOPE


def _times(line, ev) -> Tuple[float, float]:
    a = line.timestamp_ns + ev.offset_ps / 1000.0
    return a, a + ev.duration_ps / 1000.0


def read_trace(path: str) -> Tuple[List[Dict], List[Tuple[str, float, float]]]:
    """(device planes, host spans) of one ``.xplane.pb`` (or the same
    gzipped).  A device plane is ``{"modules": [(program, start, end)],
    "ops": [(scope, start, end)]}``, from its "XLA Modules" and "XLA Ops"
    lines; host spans are ``(name, start, end)`` of the program's spans
    and the benchmark's window, in ns on the trace's clock."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        space = parse_xspace(f.read())
    devices, spans = [], []
    for plane in space.planes:
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        tf_op = {k for k, v in stat_names.items() if v == "tf_op"}
        meta = {e.key: e.value for e in plane.event_metadata}
        if plane.name.startswith("/device:TPU"):
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev["modules"] += [
                        (program_name(meta[ev.metadata_id].name),
                         *_times(line, ev)) for ev in line.events]
                elif line.name == "XLA Ops":
                    scopes = {}
                    for ev in line.events:
                        if ev.metadata_id not in scopes:
                            md = meta[ev.metadata_id]
                            scopes[ev.metadata_id] = scope_of(next(
                                (s.str_value for s in md.stats
                                 if s.metadata_id in tf_op), ""))
                        dev["ops"].append((scopes[ev.metadata_id],
                                           *_times(line, ev)))
            devices.append(dev)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    name = span_name(meta[ev.metadata_id].name)
                    if name.startswith(SPAN_PREFIX) or name == WINDOW_SPAN:
                        spans.append((name, *_times(line, ev)))
    return devices, spans


def _window(devices, spans) -> Tuple[float, float]:
    windows = [(a, b) for n, a, b in spans if n == WINDOW_SPAN]
    if windows:
        return windows[0]
    every = [t for d in devices for _, a, b in d["ops"] + d["modules"]
             for t in (a, b)]
    return (min(every), max(every)) if every else (0.0, 0.0)


def _leaves(ops):
    """Operations that hold no other: a control-flow op (a while loop, a
    conditional) spans its body's operations and is dropped for them."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [o for i, o in enumerate(ops)
            if i + 1 == len(ops) or not (ops[i + 1][1] < o[2]
                                          and ops[i + 1][2] <= o[2])]


def scope_seconds(devices, lo: float, hi: float,
                  program: str = STEP_PROGRAM) -> Dict:
    """Device seconds per scope over the executions of ``program`` that lie
    wholly in [lo, hi], mean over the devices."""
    scopes: Dict[str, float] = defaultdict(float)
    steps = step_ns = 0.0
    for dev in devices:
        runs = sorted((a, b) for name, a, b in dev["modules"]
                      if name == program and lo <= a and b <= hi)
        starts = [a for a, _ in runs]
        steps += len(runs)
        step_ns += sum(b - a for a, b in runs)
        for scope, a, b in _leaves(dev["ops"]):
            i = bisect.bisect_right(starts, a) - 1
            if i >= 0 and a < runs[i][1]:
                scopes[scope] += (min(b, runs[i][1]) - a) * 1e-9
    n = max(len(devices), 1)
    return {"steps": steps / n, "step_s": step_ns * 1e-9 / n,
            "scope_s": {k: v / n for k, v in scopes.items()}}


def sync_seconds(devices, spans, lo: float, hi: float,
                 program: str = STEP_PROGRAM) -> List[float]:
    """For each execution of ``program`` ending in [lo, hi] on the first
    device: the end of the first ``serve.engine.sync`` that ends after it,
    minus the execution's end."""
    if not devices:
        return []
    ends = sorted(b for n, _, b in spans if n == SYNC_SPAN)
    out = []
    for name, _, b in sorted(devices[0]["modules"], key=lambda m: m[2]):
        if name == program and lo <= b <= hi:
            i = bisect.bisect_left(ends, b)
            if i < len(ends):
                out.append((ends[i] - b) * 1e-9)
    return out


def idle_split(devices, spans, lo: float, hi: float) -> List[List]:
    """The device's idle time in [lo, hi], each instant charged to the
    innermost (shortest) program span open at it on any host thread, mean
    over the devices; sums to the idle time."""
    inner = sorted((a, b, n) for n, a, b in spans if n != WINDOW_SPAN)
    starts = [a for a, _, _ in inner]
    longest = max((b - a for a, b, _ in inner), default=0.0)
    idle: Dict[str, float] = defaultdict(float)
    for dev in devices:
        ops = dev["ops"] or [(None, a, b) for _, a, b in dev["modules"]]
        busy = union([(max(a, lo), min(b, hi)) for _, a, b in ops
                      if b > lo and a < hi])
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for ga, gb in zip(edges[::2], edges[1::2]):
            if gb <= ga:
                continue
            i = bisect.bisect_left(starts, ga - longest)
            near = [s for s in inner[i:bisect.bisect_right(starts, gb)]
                    if s[1] > ga]
            cuts = sorted({ga, gb} | {t for a, b, _ in near
                                      for t in (a, b) if ga < t < gb})
            for a, b in zip(cuts, cuts[1:]):
                mid = (a + b) / 2
                open_ = [(sb - sa, n) for sa, sb, n in near
                         if sa <= mid < sb]
                idle[min(open_)[1] if open_ else NO_SPAN] += (b - a) * 1e-9
    n = max(len(devices), 1)
    return sorted(([k, v / n] for k, v in idle.items()),
                  key=lambda kv: -kv[1])


def span_max(spans, lo: float, hi: float) -> Dict[str, float]:
    """The longest instance of each program span that starts in [lo, hi]."""
    out: Dict[str, float] = {}
    for n, a, b in spans:
        if n != WINDOW_SPAN and lo <= a < hi:
            out[n] = max(out.get(n, 0.0), (b - a) * 1e-9)
    return out


def reduce_path(path: str) -> Dict:
    devices, spans = read_trace(path)
    lo, hi = _window(devices, spans)
    return {"devices": len(devices), "window_s": (hi - lo) * 1e-9,
            **scope_seconds(devices, lo, hi),
            "sync_s": sync_seconds(devices, spans, lo, hi),
            "idle_split": idle_split(devices, spans, lo, hi),
            "span_max_s": span_max(spans, lo, hi)}


@functools.lru_cache(maxsize=2)
def _reduce_cached(path: str, mtime: float) -> Dict:
    return reduce_path(path)


def run_trace(run: Dict, root: str = TRACE_ROOT) -> Optional[Dict]:
    """The reduction of the trace that the run ``run`` (what the harness
    hands a reader) recorded: the newest under ``root``, written after the
    run's window opened and as long as its traced part.  None when there
    is none or it cannot be read."""
    path = find_xplane(root)
    if path is None:
        return None
    opened = time.time() - (time.monotonic() - run["t0"])
    mtime = os.path.getmtime(path)
    if mtime < opened:
        return None
    try:
        red = _reduce_cached(path, mtime)
    except Exception:           # a trace this reader cannot parse
        return None
    if abs(red["window_s"] - (run["t1"] - run["t0"])) > 0.5:
        return None
    return red


def scope_ms_per_step(run: Dict, scope: str) -> Optional[float]:
    """Device milliseconds of ``scope`` per step execution, or None when
    the trace holds no step execution or no op of the scope."""
    red = run_trace(run)
    if not red or not red["steps"] or scope not in red["scope_s"]:
        return None
    return 1e3 * red["scope_s"][scope] / red["steps"]


def median_sync_ms(run: Dict) -> Optional[float]:
    red = run_trace(run)
    if not red or not red["sync_s"]:
        return None
    return 1e3 * statistics.median(red["sync_s"])
