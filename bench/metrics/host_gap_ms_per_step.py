"""Scheduler: the program's ``serve.engine.host_gap`` counter (a status
pull's return to the next admission or step having been dispatched: the
host's own share of device idle), seconds over gaps in the traced part of
the window (ms per step).  The counter's values at the window's start and
end are the program's own snapshots taken there (``Frontend.metrics()``),
found among ``repro.serving.tracing.recent()`` by their instants."""

SECONDS = "serve_engine_host_gap_seconds_total"
COUNT = "serve_engine_host_gap_count"
NEAR_S = 1.0    # a snapshot this close to an edge of the window was its


def _at(snaps, t):
    when, snap = min(snaps, key=lambda s: abs(s[0] - t))
    return snap if abs(when - t) <= NEAR_S else None


def read(run):
    try:
        from repro.serving import tracing
    except ImportError:         # a program without its own counters
        return None
    snaps = tracing.recent()
    if not snaps:
        return None
    a, b = _at(snaps, run["t0"]), _at(snaps, run["t1"])
    if a is None or b is None or a is b:
        return None
    gaps = b.get(COUNT, 0) - a.get(COUNT, 0)
    if gaps <= 0:
        return None
    return 1e3 * (b[SECONDS] - a.get(SECONDS, 0.0)) / gaps
