"""Spans and counters the benchmark takes around its calls into each layer.

No span goes inside the program: the probe wraps the bound methods of one
serving stack's objects (``Frontend`` → ``Scheduler`` →
``ContinuousBatchingEngine``) with ``jax.profiler.TraceAnnotation``s, which
cost next to nothing while no trace is taken, and samples a few counters
at the same boundaries:

  bench.frontend.tick          one serve-loop tick (executor thread)
  bench.scheduler.step         admission + one engine step
  bench.engine.admit           one admission (padded prefill + scatter)
  bench.engine.step            one decode step of every active group
  bench.engine.harvest         retirement of a group's finished slots
  bench.engine.poll_progress   the streaming pull of committed tokens
"""
from __future__ import annotations

import functools
import gc
import time
from typing import Callable, List, Tuple

from jax.profiler import TraceAnnotation


def spanned(name: str, fn: Callable) -> Callable:
    @functools.wraps(fn)
    def call(*args, **kw):
        with TraceAnnotation(name):
            return fn(*args, **kw)
    return call


class Probe:
    """Counters, each a list of (monotonic time, value) samples:

    ticks   active slots in the tick's engine step (0 when it stepped none)
    steps   contexts (tokens held) of the active slots at each engine step
    admits  queue wait of each admission: admit time minus arrival

    and ``gc_pauses``, (start, seconds) of each of Python's garbage
    collections until ``close``.
    """

    def __init__(self, frontend):
        self.ticks: List[Tuple[float, int]] = []
        self.steps: List[Tuple[float, List[int]]] = []
        self.admits: List[Tuple[float, float]] = []
        self.gc_pauses: List[Tuple[float, float]] = []
        self._gc_start = 0.0
        gc.callbacks.append(self._gc)
        self._active = 0
        sched, eng = frontend.scheduler, frontend.engine
        self.num_slots = eng.ecfg.num_slots
        frontend._tick = self._tick(frontend._tick)
        sched.step = spanned("bench.scheduler.step", sched.step)
        eng.admit = self._admit(spanned("bench.engine.admit", eng.admit))
        eng.step = self._step(eng, spanned("bench.engine.step", eng.step))
        eng._harvest_group = spanned("bench.engine.harvest",
                                     eng._harvest_group)
        eng.poll_progress = spanned("bench.engine.poll_progress",
                                    eng.poll_progress)

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_start = time.monotonic()
        else:
            self.gc_pauses.append((self._gc_start,
                                   time.monotonic() - self._gc_start))

    def close(self) -> None:
        gc.callbacks.remove(self._gc)

    def _tick(self, fn):
        fn = spanned("bench.frontend.tick", fn)

        def tick():
            self._active = 0
            out = fn()
            self.ticks.append((time.monotonic(), self._active))
            return out
        return tick

    def _admit(self, fn):
        def admit(req, *, now=None):
            t = time.monotonic()
            out = fn(req, now=now)
            self.admits.append((t, t - req.arrival))
            return out
        return admit

    def _step(self, eng, fn):
        def step(*, now=None):
            contexts = [meta["prompt_len"] + meta["emitted"]
                        for g in eng.groups
                        for i, meta in enumerate(g.slot_meta)
                        if meta is not None and g.status[i] & 1]
            self._active = len(contexts)
            self.steps.append((time.monotonic(), contexts))
            return fn(now=now)
        return step
