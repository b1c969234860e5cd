"""Spans and counters of the serving program, always on.

``span(name, **ids)`` times a block of host code.  It enters a
``jax.profiler.TraceAnnotation``, so a profiler trace shows the block on
the host plane, on the same clock as the device planes (about 1 µs when no
trace is taken).  It also adds the block's duration to three counters kept
per name: ``count``, ``seconds_total`` and ``seconds_max``.
``record(name, seconds)`` adds a duration measured by other means (one that
crosses threads) to the same counters.  ``seconds_max`` is the longest
single instance since the previous ``snapshot()``, which resets it, like a
scrape-window maximum: only ``GET /metrics`` and a benchmark's own
snapshots should take one.

The module also counts the backend compiles of this process
(``compiles_total``, ``compile_seconds_total``), from ``jax.monitoring``'s
compile event; the listener is registered once, at import.

Every span and counter name starts with ``serve.``; in ``snapshot()`` the
dots become underscores, so ``serve.engine.sync`` is exported as
``serve_engine_sync_count``, ``serve_engine_sync_seconds_total`` and
``serve_engine_sync_seconds_max``.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Deque, Dict, List, Tuple

import jax
from jax.profiler import TraceAnnotation

__all__ = ["span", "record", "snapshot", "recent", "totals"]

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_RECENT = 4             # snapshots kept, with their instants

_lock = threading.Lock()
_stats: Dict[str, List[float]] = {}   # name -> [count, total s, max s]
_compiles = [0, 0.0]                  # backend compiles, their seconds
_recent: Deque[Tuple[float, Dict[str, float]]] = deque(maxlen=_RECENT)


def record(name: str, seconds: float) -> None:
    """Add one instance of ``seconds`` to the counters of ``name``."""
    with _lock:
        s = _stats.get(name)
        if s is None:
            _stats[name] = [1, seconds, seconds]
        else:
            s[0] += 1
            s[1] += seconds
            if seconds > s[2]:
                s[2] = seconds


class span:
    """``with span("serve.engine.sync"):`` — a profiler annotation plus the
    counters of its name.  Keyword ``ids`` (a request's ``rid``, a count)
    go into the annotation only."""

    __slots__ = ("_name", "_ann", "_t0")

    def __init__(self, name: str, **ids):
        self._name = name
        self._ann = TraceAnnotation(name, **ids)

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        record(self._name, dt)
        return False


def _on_duration(event: str, duration: float, **_) -> None:
    if event == _COMPILE_EVENT:
        with _lock:
            _compiles[0] += 1
            _compiles[1] += duration


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def totals() -> Dict[str, Dict[str, float]]:
    """``{name: {"count", "seconds_total", "seconds_max"}}`` now, without
    resetting anything."""
    with _lock:
        return {n: {"count": c, "seconds_total": t, "seconds_max": m}
                for n, (c, t, m) in _stats.items()}


def snapshot() -> Dict[str, float]:
    """Every counter as a flat ``{key: value}`` for ``/metrics``; resets
    each ``seconds_max``.  The snapshot is also kept, with its
    ``time.monotonic()`` instant, among the last few (``recent()``)."""
    out: Dict[str, float] = {}
    with _lock:
        for name, s in _stats.items():
            key = name.replace(".", "_")
            out[f"{key}_count"] = s[0]
            out[f"{key}_seconds_total"] = s[1]
            out[f"{key}_seconds_max"] = s[2]
            s[2] = 0.0
        out["compiles_total"] = _compiles[0]
        out["compile_seconds_total"] = _compiles[1]
        _recent.append((time.monotonic(), out))
    return dict(out)


def recent() -> List[Tuple[float, Dict[str, float]]]:
    """The last few snapshots as ``(time.monotonic() instant, snapshot)``,
    oldest first: two of them give every counter over the window
    between."""
    with _lock:
        return list(_recent)
