"""Continuous-batching serving for blockwise parallel decoding.

Layering:
  types.py     — Request / FinishedRequest / PreemptedRequest /
                 EngineConfig / SlotBatch
  session.py   — DecodeSession: sharding-aware owner of params + the jitted
                 decode functions (shared with core.decode entry points)
  engine.py    — scheduler + slot-metadata shell over a DecodeSession
  scheduler.py — queue, admission policy, priorities/deadlines/preemption,
                 workload driver, stats
  frontend.py  — asyncio facade: per-request token streams + back-pressure
  server.py    — stdlib HTTP/1.1 + SSE surface over the frontend
  tracing.py   — ``serve.*`` spans (profiler annotations plus counters
                 exported on /metrics) and the compile counter
"""
from repro.serving.engine import (ContinuousBatchingEngine, PagePoolExhausted,
                                  PolicyGroup)
from repro.serving.frontend import Backpressure, Frontend, StreamEvent
from repro.serving.scheduler import Scheduler, aggregate_stats
from repro.serving.server import HTTPServer
from repro.serving.session import DecodeSession, ServingFns
from repro.serving.types import (EngineConfig, FinishedRequest,
                                 PreemptedRequest, Request, SlotBatch)

__all__ = [
    "Backpressure",
    "ContinuousBatchingEngine",
    "DecodeSession",
    "Frontend",
    "HTTPServer",
    "PagePoolExhausted",
    "PolicyGroup",
    "PreemptedRequest",
    "ServingFns",
    "SlotBatch",
    "StreamEvent",
    "Scheduler",
    "aggregate_stats",
    "EngineConfig",
    "FinishedRequest",
    "Request",
]
