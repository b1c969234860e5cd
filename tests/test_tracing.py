"""Spans and counters of the serving program (``serving/tracing.py``):
counts, totals and window maxima, the compile counter, the spans' cover of
the serve loop, the ``/metrics`` keys, and the named scopes the compiled
step and admit programs carry."""
import asyncio
import http.client
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_dense
from repro.config import DecodeConfig
from repro.models import model as M
from repro.serving import (
    ContinuousBatchingEngine,
    EngineConfig,
    Frontend,
    HTTPServer,
    Scheduler,
    tracing,
)

pytestmark = pytest.mark.serving

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src", "repro")
ENGINE_SPANS = ("serve.frontend.tick", "serve.frontend.loop",
                "serve.frontend.hop", "serve.scheduler.step",
                "serve.engine.admit", "serve.engine.admit_prep",
                "serve.engine.dispatch", "serve.engine.sync",
                "serve.engine.harvest", "serve.engine.poll",
                "serve.engine.poll_sync", "serve.engine.host_gap")
STEP_SCOPES = ("bpd.trunk", "bpd.heads", "bpd.verify", "bpd.commit",
               "bpd.draft")
ADMIT_SCOPES = ("bpd.prefill", "bpd.install")


def test_span_counts_totals_and_window_max():
    before = tracing.totals().get("serve.test.outer", {"count": 0,
                                                        "seconds_total": 0})
    for _ in range(3):
        with tracing.span("serve.test.outer", rid=7):
            with tracing.span("serve.test.inner"):
                pass
    tracing.record("serve.test.outer", 0.25)
    now = tracing.totals()
    outer, inner = now["serve.test.outer"], now["serve.test.inner"]
    assert outer["count"] - before["count"] == 4
    assert 0.25 <= outer["seconds_total"] - before["seconds_total"] < 0.3
    assert outer["seconds_max"] == pytest.approx(0.25)
    assert inner["count"] >= 3
    # a nested span lies inside its parent
    assert inner["seconds_max"] <= outer["seconds_max"]

    snap = tracing.snapshot()
    assert snap["serve_test_outer_seconds_max"] == pytest.approx(0.25)
    assert snap["serve_test_outer_count"] == outer["count"]
    assert {"compiles_total", "compile_seconds_total"} <= set(snap)
    # the snapshot restarted the window maximum, not the totals
    again = tracing.snapshot()
    assert again["serve_test_outer_seconds_max"] == 0.0
    assert again["serve_test_outer_seconds_total"] == pytest.approx(
        snap["serve_test_outer_seconds_total"])
    t_last, last = tracing.recent()[-1]
    assert last == again and t_last > tracing.recent()[-2][0]


def test_a_span_still_counts_when_its_block_raises():
    n = tracing.totals().get("serve.test.raises", {"count": 0})["count"]
    with pytest.raises(ValueError):
        with tracing.span("serve.test.raises"):
            raise ValueError("x")
    assert tracing.totals()["serve.test.raises"]["count"] == n + 1


def test_span_and_counter_names():
    """Every span and counter the program names starts with ``serve.``;
    none with ``bench.``, which is the benchmark's own."""
    names = set()
    for d, _, files in os.walk(SRC):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as fh:
                    names |= set(re.findall(
                        r"tracing\.(?:span|record)\(\s*\"([^\"]+)\"",
                        fh.read()))
    assert set(ENGINE_SPANS) <= names
    assert all(n.startswith("serve.") for n in names), names


def test_compile_counter_moves_on_a_fresh_jit():
    before = tracing.snapshot()
    jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)(
        jnp.ones((3, 5, 7))).block_until_ready()
    after = tracing.snapshot()
    assert after["compiles_total"] > before["compiles_total"]
    assert after["compile_seconds_total"] > before["compile_seconds_total"]


@pytest.fixture(scope="module")
def smoke():
    cfg = tiny_dense()
    params = M.init(jax.random.PRNGKey(0), cfg)
    dec = DecodeConfig(max_new_tokens=12, block_k=4)
    ecfg = EngineConfig(num_slots=2, max_prompt_len=16, max_new_cap=12)
    return params, cfg, dec, ecfg


def _requests(cfg, n, seed=3):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, cfg.vocab_size, int(rng.integers(3, 12))),
             int(rng.integers(6, 13))) for _ in range(n)]


async def _serve(frontend, reqs):
    await frontend.start()
    streams = [frontend.submit(p, m)[1] for p, m in reqs[:2]]
    for p, m in reqs[2:]:
        await asyncio.sleep(0)
        while frontend.queue_depth() >= frontend.max_queue:
            await asyncio.sleep(0.001)
        streams.append(frontend.submit(p, m)[1])
    for q in streams:
        while (await q.get()).kind != "done":
            pass
    await frontend.stop()


def test_spans_tile_the_serve_loop(smoke, monkeypatch):
    """Between the first and the last step dispatch, the serve loop's
    spans plus the hand-offs counted in ``serve.frontend.hop`` cover at
    least 95% of the wall time."""
    params, cfg, dec, ecfg = smoke
    eng = ContinuousBatchingEngine(params, cfg, dec, ecfg)
    frontend = Frontend(Scheduler(eng), max_queue=4)
    asyncio.run(_serve(frontend, _requests(cfg, 2)))     # compiles
    seen = []
    record = tracing.record

    def logged(name, seconds):
        t = time.perf_counter()
        seen.append((name, t - seconds, t))
        record(name, seconds)

    monkeypatch.setattr(tracing, "record", logged)
    frontend = Frontend(Scheduler(eng), max_queue=4)
    asyncio.run(_serve(frontend, _requests(cfg, 6)))
    dispatches = [a for n, a, _ in seen if n == "serve.engine.dispatch"]
    assert len(dispatches) >= 10
    lo, hi = dispatches[0], dispatches[-1]
    spans = sorted((max(a, lo), min(b, hi)) for n, a, b in seen
                   if n in ENGINE_SPANS and b > lo and a < hi)
    covered, end = 0.0, lo
    for a, b in spans:
        if b > end:
            covered += b - max(a, end)
            end = b
    assert covered >= 0.95 * (hi - lo), (covered, hi - lo)
    # the host gaps: one per step that had a step or admission after it
    gaps = [b - a for n, a, b in seen if n == "serve.engine.host_gap"]
    assert gaps and all(g >= 0 for g in gaps)
    assert len(gaps) <= len(dispatches) + 6


def _metrics(srv):
    conn = http.client.HTTPConnection(srv.host, srv.port, timeout=30)
    conn.request("GET", "/metrics")
    raw = conn.getresponse().read()
    conn.close()
    out = {}
    for ln in raw.decode().splitlines():
        k, v = ln.rsplit(" ", 1)
        out[k.removeprefix("repro_serving_")] = float(v)
    return out


def test_metrics_serve_every_span(smoke):
    """``GET /metrics`` serves count, total and window maximum for every
    span and counter of the serve loop, the host gap and the compile
    counter; the engine's old phase timers are gone.  A scrape restarts
    the window maximum."""
    params, cfg, dec, ecfg = smoke
    eng = ContinuousBatchingEngine(params, cfg, dec, ecfg)
    frontend = Frontend(Scheduler(eng), max_queue=4)

    async def scrape():
        srv = HTTPServer(frontend, port=0)
        await srv.start()
        streams = [frontend.submit(p, m)[1] for p, m in _requests(cfg, 2)]
        for q in streams:
            while (await q.get()).kind != "done":
                pass
        loop = asyncio.get_running_loop()
        first = await loop.run_in_executor(None, _metrics, srv)
        second = await loop.run_in_executor(None, _metrics, srv)
        await srv.stop()
        return first, second

    first, second = asyncio.run(scrape())
    for name in ENGINE_SPANS:
        key = name.replace(".", "_")
        for field in ("count", "seconds_total", "seconds_max"):
            assert f"{key}_{field}" in first, (key, field)
        assert first[f"{key}_count"] > 0, key
    assert first["serve_engine_host_gap_seconds_total"] > 0
    assert first["compiles_total"] > 0
    assert not any(k.startswith("time_in_") for k in first)
    # the first scrape's window held the requests; the second's held none
    assert first["serve_engine_sync_seconds_max"] > 0
    assert second["serve_engine_sync_seconds_max"] == 0
    assert frontend.active_slots() == 0


def test_compiled_programs_carry_the_scopes(smoke):
    """The step and admit programs keep their names and carry every
    ``bpd.*`` scope in their ops' ``op_name`` metadata."""
    params, cfg, dec, ecfg = smoke
    eng = ContinuousBatchingEngine(params, cfg, dec, ecfg)
    g = eng.groups[0]
    step = g.fns.step.lower(eng.params, eng.aux_params, g.state)
    admit = g.fns.admit.lower(
        eng.params, eng.aux_params, g.state, jnp.asarray(0, jnp.int32),
        jnp.zeros((ecfg.max_prompt_len,), jnp.int32),
        jnp.asarray(3, jnp.int32), jnp.asarray(4, jnp.int32),
        jnp.zeros((ecfg.max_prompt_len,), jnp.int32))
    for lowered, name, scopes in ((step, "step_windowed", STEP_SCOPES),
                                  (admit, "admit", ADMIT_SCOPES)):
        text = lowered.compile().as_text()
        assert text.startswith(f"HloModule jit_{name}"), text[:80]
        op_names = set(re.findall(r'op_name="([^"]+)"', text))
        for scope in scopes:
            assert any(f"/{scope}/" in n for n in op_names), (name, scope)
