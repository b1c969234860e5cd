"""HTTP/SSE server contract over a real socket: health/readiness/metrics,
token-exact streaming (SSE and collected JSON), validation errors,
deterministic 429 back-pressure with Retry-After, and priority preemption
driven entirely over the wire."""
import asyncio
import http.client
import json
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_dense
from repro.config import DecodeConfig
from repro.core import decode as D
from repro.models import model as M
from repro.serving import (
    ContinuousBatchingEngine,
    EngineConfig,
    Frontend,
    HTTPServer,
    Scheduler,
)

pytestmark = pytest.mark.serving

MAX_NEW = 16


@pytest.fixture(scope="module")
def server():
    """One live server shared by every test here: the event loop runs in a
    background thread, tests speak plain HTTP/1.1 from the test thread.
    eos -1 keeps every request at its full budget, which makes slot
    occupancy (and therefore 429s and preemption) deterministic."""
    cfg = tiny_dense()
    params = M.init(jax.random.PRNGKey(0), cfg)
    dec = DecodeConfig(max_new_tokens=MAX_NEW, block_k=4)
    eng = ContinuousBatchingEngine(
        params, cfg, dec, EngineConfig(num_slots=2, max_prompt_len=24,
                                       max_new_cap=MAX_NEW))
    fe = Frontend(Scheduler(eng), max_queue=2)
    srv = HTTPServer(fe, port=0)                # ephemeral port
    loop = asyncio.new_event_loop()
    thread = threading.Thread(target=loop.run_forever, daemon=True)
    thread.start()
    asyncio.run_coroutine_threadsafe(srv.start(), loop).result(timeout=300)
    yield params, cfg, dec, srv
    asyncio.run_coroutine_threadsafe(srv.stop(), loop).result(timeout=60)
    loop.call_soon_threadsafe(loop.stop)
    thread.join(timeout=10)


def _request(srv, method, path, body=None):
    conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=300)
    if body is not None and not isinstance(body, (str, bytes)):
        body = json.dumps(body)
    conn.request(method, path, body=body)
    resp = conn.getresponse()
    raw = resp.read()           # Connection: close -> EOF ends the stream
    headers = dict(resp.getheaders())
    conn.close()
    return resp.status, headers, raw


def _sse_events(raw):
    events = []
    for block in raw.decode().split("\n\n"):
        ev = data = None
        for ln in block.split("\n"):
            if ln.startswith("event: "):
                ev = ln[len("event: "):]
            elif ln.startswith("data: "):
                data = json.loads(ln[len("data: "):])
        if ev is not None:
            events.append((ev, data))
    return events


def _metrics_map(srv):
    _, _, raw = _request(srv, "GET", "/metrics")
    out = {}
    for ln in raw.decode().splitlines():
        k, v = ln.rsplit(" ", 1)
        out[k.removeprefix("repro_serving_")] = float(v)
    return out


def _reference(params, cfg, dec, prompt, max_new):
    d1 = dec.replace(max_new_tokens=max_new)
    bt, bs = D.bpd_decode(params, cfg, d1,
                          {"tokens": jnp.asarray(prompt)[None]})
    n = int(bs["text_len"][0])
    return [int(t) for t in np.asarray(bt[0, len(prompt):n])]


def test_health_ready_metrics(server):
    *_, srv = server
    status, _, raw = _request(srv, "GET", "/healthz")
    assert status == 200 and raw == b"ok\n"
    status, _, raw = _request(srv, "GET", "/readyz")
    assert status == 200 and raw == b"ready\n"
    m = _metrics_map(srv)
    assert m["num_slots"] == 2
    for key in ("requests_total", "rejected_total", "preemptions_total",
                "backpressure_requeues_total", "engine_steps_total"):
        assert key in m


def test_stream_matches_reference(server):
    """The SSE token events concatenate to exactly the run-to-completion
    bpd_decode output, and the done payload agrees with them."""
    params, cfg, dec, srv = server
    prompt = np.random.default_rng(19).integers(0, cfg.vocab_size, size=6)
    status, headers, raw = _request(
        srv, "POST", "/v1/generate",
        {"prompt": prompt.tolist(), "max_new": MAX_NEW})
    assert status == 200
    assert headers["Content-Type"] == "text/event-stream"
    events = _sse_events(raw)
    toks = [t for ev, d in events if ev == "token" for t in d["tokens"]]
    dones = [d for ev, d in events if ev == "done"]
    assert len(dones) == 1 and events[-1][0] == "done"
    done = dones[0]
    ref = _reference(params, cfg, dec, prompt, MAX_NEW)
    assert toks == done["tokens"] == ref
    assert done["generated"] == len(ref)
    assert done["preempted"] == 0
    assert done["invocations"] >= 2 and done["mean_accepted"] > 0
    assert done["latency_s"] >= done["queue_delay_s"] >= 0


def test_nonstream_json_matches_reference(server):
    params, cfg, dec, srv = server
    prompt = np.random.default_rng(20).integers(0, cfg.vocab_size, size=5)
    status, headers, raw = _request(
        srv, "POST", "/v1/generate",
        {"prompt": prompt.tolist(), "max_new": 8, "stream": False})
    assert status == 200
    assert headers["Content-Type"] == "application/json"
    done = json.loads(raw)
    assert done["tokens"] == _reference(params, cfg, dec, prompt, 8)


def test_validation_errors(server):
    *_, srv = server
    status, _, raw = _request(srv, "POST", "/v1/generate", "{not json")
    assert status == 400 and b"prompt" in raw
    status, _, _ = _request(srv, "POST", "/v1/generate", {"prompt": [1, 2]})
    assert status == 400                          # max_new missing
    status, _, raw = _request(
        srv, "POST", "/v1/generate",
        {"prompt": list(range(1, 40)), "max_new": 4})
    assert status == 400 and b"prompt length" in raw
    status, _, raw = _request(
        srv, "POST", "/v1/generate",
        {"prompt": [1, 2, 3], "max_new": 4, "policy": "no-such-policy"})
    assert status == 400
    status, _, _ = _request(srv, "GET", "/v1/generate")
    assert status == 404
    status, _, _ = _request(srv, "GET", "/nope")
    assert status == 404


def test_backpressure_429_with_retry_after(server):
    """A 12-request burst against 2 slots + 2 queue spots must reject some
    requests with 429 + Retry-After; accepted streams stay token-exact."""
    params, cfg, dec, srv = server
    rng = np.random.default_rng(29)
    prompts = [rng.integers(0, cfg.vocab_size, size=5) for _ in range(12)]

    def one(i):
        return _request(srv, "POST", "/v1/generate",
                        {"prompt": prompts[i].tolist(), "max_new": MAX_NEW})

    with ThreadPoolExecutor(max_workers=12) as ex:
        out = list(ex.map(one, range(12)))
    statuses = [s for s, _, _ in out]
    assert statuses.count(200) >= 2               # capacity was served
    assert 429 in statuses                        # overflow was refused
    _, hdrs, raw = out[statuses.index(429)]
    assert int(hdrs["Retry-After"]) >= 1
    body = json.loads(raw)
    assert body["retry_after_s"] >= 1 and "retry" in body["error"]
    assert _metrics_map(srv)["rejected_total"] >= statuses.count(429)
    for (status, _, raw), p in zip(out, prompts):
        if status == 200:
            done = [d for ev, d in _sse_events(raw) if ev == "done"][0]
            assert done["tokens"] == _reference(params, cfg, dec, p, MAX_NEW)


def test_preemption_over_the_wire(server):
    """Fill both slots with full-budget requests, then send a priority-1
    past-deadline request: one victim is evicted and re-admitted, yet every
    stream — victims included — is token-identical to an uninterrupted
    decode."""
    params, cfg, dec, srv = server
    rng = np.random.default_rng(23)
    prompts = [rng.integers(0, cfg.vocab_size, size=6) for _ in range(3)]
    results = {}

    def client(i, payload):
        status, _, raw = _request(srv, "POST", "/v1/generate", payload)
        results[i] = (status, _sse_events(raw))

    base = _metrics_map(srv)
    with ThreadPoolExecutor(max_workers=2) as ex:
        futs = [ex.submit(client, i, {"prompt": prompts[i].tolist(),
                                      "max_new": MAX_NEW})
                for i in range(2)]
        # wait until both occupy slots and the queue is empty: the urgent
        # request below then CANNOT be served without evicting someone
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            m = _metrics_map(srv)
            if m["active_slots"] >= 2 and m["queue_depth"] == 0:
                break
            time.sleep(0.002)
        else:
            pytest.fail("slots never filled")
        client(2, {"prompt": prompts[2].tolist(), "max_new": 4,
                   "priority": 1, "deadline_s": 0.0})
        for f in futs:
            f.result()

    assert all(results[i][0] == 200 for i in range(3))
    dones = {i: [d for ev, d in results[i][1] if ev == "done"][0]
             for i in range(3)}
    assert dones[2]["preempted"] == 0             # the urgent one never waits
    assert sum(dones[i]["preempted"] for i in (0, 1)) >= 1
    m = _metrics_map(srv)
    assert m["preemptions_total"] >= base["preemptions_total"] + 1
    for i, budget in ((0, MAX_NEW), (1, MAX_NEW), (2, 4)):
        toks = [t for ev, d in results[i][1] if ev == "token"
                for t in d["tokens"]]
        ref = _reference(params, cfg, dec, prompts[i], budget)
        assert toks == dones[i]["tokens"] == ref, f"rid-slot {i}"


def test_graceful_drain_over_the_wire():
    """POST /drain against a live server: 202 immediately,
    readiness flips to 503 "draining", new submissions are refused with
    503, the in-flight stream finishes token-exact, and the listener then
    closes — the whole SIGTERM shutdown path, driven over the wire (the
    signal handler and this route share ``begin_drain``).  Needs its own
    server: a drained listener cannot be reused by later tests."""
    cfg = tiny_dense()
    params = M.init(jax.random.PRNGKey(0), cfg)
    dec = DecodeConfig(max_new_tokens=MAX_NEW, block_k=4)
    eng = ContinuousBatchingEngine(
        params, cfg, dec,
        EngineConfig(num_slots=2, max_prompt_len=24, max_new_cap=MAX_NEW))
    srv = HTTPServer(Frontend(Scheduler(eng), max_queue=2), port=0)
    loop = asyncio.new_event_loop()
    thread = threading.Thread(target=loop.run_forever, daemon=True)
    thread.start()
    asyncio.run_coroutine_threadsafe(srv.start(), loop).result(timeout=300)
    try:
        rng = np.random.default_rng(31)
        prompt = rng.integers(0, cfg.vocab_size, size=6)
        results = {}

        def client():
            results["r"] = _request(
                srv, "POST", "/v1/generate",
                {"prompt": prompt.tolist(), "max_new": MAX_NEW})

        t = threading.Thread(target=client)
        t.start()
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            if _metrics_map(srv)["active_slots"] >= 1:
                break
            time.sleep(0.002)
        else:
            pytest.fail("in-flight request never occupied a slot")

        status, _, raw = _request(srv, "POST", "/drain")
        assert status == 202
        body = json.loads(raw)
        assert body["draining"] is True and body["in_flight"] >= 1
        status, _, _ = _request(srv, "POST", "/drain")   # idempotent
        assert status == 202
        status, _, raw = _request(srv, "GET", "/readyz")
        assert status == 503 and raw == b"draining\n"
        status, _, raw = _request(srv, "POST", "/v1/generate",
                                  {"prompt": [1, 2, 3], "max_new": 4})
        assert status == 503 and b"drain" in raw

        t.join(timeout=120)
        assert not t.is_alive(), "in-flight stream did not finish"
        status, _, raw = results["r"]
        assert status == 200
        done = [d for ev, d in _sse_events(raw) if ev == "done"][0]
        assert done["tokens"] == _reference(params, cfg, dec, prompt,
                                            MAX_NEW)
        # drained + flushed -> the listener closes; new connections refuse
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            try:
                _request(srv, "GET", "/healthz")
                time.sleep(0.01)
            except OSError:
                break
        else:
            pytest.fail("listener never closed after the drain finished")
    finally:
        asyncio.run_coroutine_threadsafe(srv.stop(), loop).result(timeout=60)
        loop.call_soon_threadsafe(loop.stop)
        thread.join(timeout=10)
