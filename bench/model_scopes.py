"""Read the model's own named scopes inside the step program from a trace.

``bench.span_reduce`` charges each device operation to the first ``bpd.*``
component of its ``op_name``; the scopes a model puts inside those
(``bpd.trunk/moe.experts``, ``bpd.trunk/mla.attend``: ``repro.models.moe``
and ``repro.models.attention``) are read here, with span_reduce's own
parser of the XSpace protobuf and its reduction per step execution.  A
fusion carries its root operation's ``op_name``.

  scope_s   {model scope: device seconds} over the executions of the step
            program that lie wholly in the traced window, with their count
            (``steps``); an op under no model scope counts as ``no scope``.

A trace without such scopes (a model that has none, or a program that
predates them) gives none, and the readers return None.
"""
from __future__ import annotations

import functools
import gzip
import os
import time
from typing import Dict, Optional

from bench import span_reduce
from bench.trace_reduce import WINDOW_SPAN, find_xplane, program_name

PREFIXES = ("moe.", "mla.")


def model_scope(op_name: str) -> str:
    """The first ``moe.*`` or ``mla.*`` component of an ``op_name``."""
    for part in op_name.split("/"):
        if part.startswith(PREFIXES):
            return part.rstrip(":")
    return span_reduce.NO_SCOPE


def read_trace(path: str):
    """(device planes, window spans) of one ``.xplane.pb`` (or the same
    gzipped), each device op charged to its model scope; the shapes of
    ``span_reduce.read_trace``."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        space = span_reduce.parse_xspace(f.read())
    devices, spans = [], []
    for plane in space.planes:
        names = {e.key: e.value.name for e in plane.stat_metadata}
        tf_op = {k for k, v in names.items() if v == "tf_op"}
        meta = {e.key: e.value for e in plane.event_metadata}
        if plane.name.startswith("/device:TPU"):
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev["modules"] += [
                        (program_name(meta[ev.metadata_id].name),
                         *span_reduce._times(line, ev)) for ev in line.events]
                elif line.name == "XLA Ops":
                    scopes = {}
                    for ev in line.events:
                        if ev.metadata_id not in scopes:
                            md = meta[ev.metadata_id]
                            scopes[ev.metadata_id] = model_scope(next(
                                (s.str_value for s in md.stats
                                 if s.metadata_id in tf_op), ""))
                        dev["ops"].append((scopes[ev.metadata_id],
                                           *span_reduce._times(line, ev)))
            devices.append(dev)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if meta[ev.metadata_id].name == WINDOW_SPAN:
                        spans.append((WINDOW_SPAN,
                                      *span_reduce._times(line, ev)))
    return devices, spans


def reduce_path(path: str) -> Dict:
    devices, spans = read_trace(path)
    lo, hi = span_reduce._window(devices, spans)
    return {"window_s": (hi - lo) * 1e-9,
            **span_reduce.scope_seconds(devices, lo, hi)}


@functools.lru_cache(maxsize=2)
def _reduce_cached(path: str, mtime: float) -> Dict:
    return reduce_path(path)


def run_scopes(run: Dict,
               root: str = span_reduce.TRACE_ROOT) -> Optional[Dict]:
    """The reduction of the trace the run ``run`` recorded, found and
    vetted as ``span_reduce.run_trace`` finds its own; None when there is
    none or it cannot be read."""
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


def prefix_ms_per_step(run: Dict, prefix: str) -> Optional[float]:
    """Device milliseconds per step execution of the ops under any scope
    starting with ``prefix``; None where the trace holds none."""
    red = run_scopes(run)
    if not red or not red["steps"]:
        return None
    parts = [s for k, s in red["scope_s"].items() if k.startswith(prefix)]
    return 1e3 * sum(parts) / red["steps"] if parts else None


def scope_s_per_step(run: Dict, scope: str) -> Optional[float]:
    red = run_scopes(run)
    if not red or not red["steps"] or scope not in red["scope_s"]:
        return None
    return red["scope_s"][scope] / red["steps"]
