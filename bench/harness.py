"""One run of one cell: set-up, pre-roll, the measured window, the check.

The path the window drives is the one users hit: ``POST /v1/generate``
with SSE on localhost, through ``HTTPServer`` → ``Frontend`` →
``Scheduler`` → ``ContinuousBatchingEngine`` → ``DecodeSession`` → model,
with the clients in the same process and event loop.

  set-up    the configuration's weights drawn on the device
            (``bench.weights``, in the layout of the configuration's family,
            ``bench/models/<model_type>.py``), the engine built, the cell's
            own shapes warmed by one request through the server (one
            admission at ``max_prompt_len``, one step, one evict), then a
            pre-roll of the same traffic that is not counted; ``setup_s``
            runs from process start to the window's start;
  window    ``--seconds`` of the traffic, timed on the client side; with
            ``--trace 1`` the profiler records its first ``TRACE_S``
            seconds and the per-layer metrics are read over that part;
  check     after the window, with the program's state freed: the served
            tokens of a sample of finished requests against the plain
            reference (``bench.check``), plus every request's own account.
"""
from __future__ import annotations

import asyncio
import gc
import math
import os
import shutil
import time
from typing import Dict, List, Optional

import numpy as np

from bench import check, load, spec, trace_reduce, traffic
from bench.probe import Probe
from bench.weights import make_params, param_structs

TRACE_S = 8.0           # traced part of a --trace 1 window
WARM_TIMEOUT_S = 600.0  # the warm-up request's first run compiles


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    import sys
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class CompileLog:
    """Backend compile seconds per program and persistent-cache hits, from
    JAX's own monitoring events (as in ``chip_smoke.py``).  One per
    process: ``CompileLog.get()``."""

    _one = None

    @classmethod
    def get(cls) -> "CompileLog":
        if cls._one is None:
            cls._one = cls()
        return cls._one

    def __init__(self):
        import jax

        self.compiles: List = []      # (monotonic time, program, seconds)
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((time.monotonic(), kw.get("fun_name", "?"),
                                  duration))

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t, _, _ in self.compiles if t0 <= t < t1)

    def report(self) -> None:
        for _, name, sec in self.compiles:
            log(f"compile {name}: {sec:.3f} s")
        log(f"compile total {sum(s for _, _, s in self.compiles):.3f} s over "
            f"{len(self.compiles)} programs, {self.cache_hits} read from the "
            f"persistent cache")


def enable_compile_cache() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``;
    every program is cached, however small or quick to compile."""
    import jax

    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(spec.CHECKOUT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def check_layout(model, c: Dict, cfg) -> None:
    """The weights the benchmark makes from the family ``model``'s layout
    have the program's tree, shapes and dtypes."""
    import jax
    from repro.models import model as M

    want = jax.eval_shape(lambda: M.init(jax.random.PRNGKey(0), cfg))
    have = param_structs(model, c)
    if (jax.tree_util.tree_structure(want)
            != jax.tree_util.tree_structure(have)
            or jax.tree_util.tree_leaves(want)
            != jax.tree_util.tree_leaves(have)):
        raise spec.SpecError(f"the program's parameter layout differs from "
                             f"the layout of bench/models/"
                             f"{c['model_type']}.py")


def make_mesh(c: Dict):
    mesh = c.get("mesh")
    if not mesh or mesh == [1, 1]:
        return None
    from repro.launch.mesh import make_host_mesh

    return make_host_mesh(data=mesh[0], model=mesh[1], require=True)


def build_frontend(params, cfg, c: Dict, mix: Dict, mesh):
    from repro.config import DecodeConfig
    from repro.serving import (ContinuousBatchingEngine, EngineConfig,
                               Frontend, Scheduler)

    geo = mix["engine"]
    dec = DecodeConfig(max_new_tokens=geo["max_new_cap"],
                       block_k=c["bpd_heads"], policy=c["policy"],
                       cache_backend=c["kv_cache"])
    ecfg = EngineConfig(num_slots=geo["num_slots"],
                        max_prompt_len=geo["max_prompt_len"],
                        max_new_cap=geo["max_new_cap"])
    engine = ContinuousBatchingEngine(params, cfg, dec, ecfg, mesh=mesh)
    return Frontend(Scheduler(engine), max_queue=mix["max_queue"])


async def serve(frontend, mix: Dict, specs, seconds: float,
                trace_dir: Optional[str], marks: Dict,
                warm_timeout: float) -> List[Dict]:
    """Warm-up, pre-roll and window through the HTTP server; returns the
    client records.  ``marks`` gets the window's instants and counters."""
    import jax
    from repro.serving import HTTPServer

    loop = asyncio.get_running_loop()
    srv = HTTPServer(frontend, host="127.0.0.1", port=0)
    await srv.start()
    geo = mix["engine"]
    warm = load.new_record(-1, specs[0].prompt[:1] * geo["max_prompt_len"],
                           1, loop.time())
    try:
        await asyncio.wait_for(load.sse_request(srv.host, srv.port, warm),
                               warm_timeout)
    except asyncio.TimeoutError:
        log(f"warm-up request not done in {warm_timeout} s")
    marks["warm_done"] = loop.time()

    records: List[Dict] = []
    t_start = loop.time()
    t0 = t_start + mix["preroll_s"]
    t1 = t0 + seconds
    if mix["loop"] == "open":
        clients = load.drive_open(srv.host, srv.port, specs, t_start, t1,
                                  records)
    else:
        firsts = traffic.first_budgets(
            [specs[i % len(specs)].max_new for i in range(mix["clients"])])
        clients = load.drive_closed(srv.host, srv.port, specs, firsts, t1,
                                    records)
    task = asyncio.ensure_future(clients)
    if trace_dir is not None:
        # the profiler starts in the pre-roll, so its start-up stall
        # falls before the window; Python function tracing stays off
        await asyncio.sleep(max(0.0, t0 - 1.0 - loop.time()))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    await asyncio.sleep(max(0.0, t0 - loop.time()))
    marks["t0"], marks["t1"] = t0, t1
    marks["counters0"] = frontend.metrics()
    if trace_dir is not None:
        marks["t_trace"] = t0 + min(TRACE_S, seconds)
        window = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
        window.__enter__()
        await asyncio.sleep(max(0.0, marks["t_trace"] - loop.time()))
        marks["counters_trace"] = frontend.metrics()
        window.__exit__(None, None, None)
        await loop.run_in_executor(None, jax.profiler.stop_trace)
    await task
    marks["counters1"] = frontend.metrics()
    # the serve loop first, then every connection still open (a handler
    # waiting for tokens that will not come notices no closed client)
    await frontend.stop()
    rest = [t for t in asyncio.all_tasks() if t is not asyncio.current_task()]
    for t in rest:
        t.cancel()
    await asyncio.gather(*rest, return_exceptions=True)
    await srv.stop()
    return records


def _pct(values: List[float], q: float) -> Optional[float]:
    return float(np.percentile(values, q)) if values else None


def window_tokens(records: List[Dict], t0: float, t1: float) -> int:
    return sum(n for r in records for t, n in r["events"] if t0 <= t < t1)


def ttft_samples(records: List[Dict], t0: float, t1: float) -> List[float]:
    """Every request due in the window: due → first token; one with no
    token when the window ends enters with its wait so far."""
    out = []
    for r in records:
        if t0 <= r["due"] < t1:
            first = r["events"][0][0] if r["events"] else math.inf
            out.append(min(first, t1) - r["due"])
    return out


def tpot_samples(records: List[Dict], t0: float, t1: float) -> List[float]:
    """Requests with at least two token events in the window: (last − first
    event time) ÷ (tokens in those events − 1)."""
    out = []
    for r in records:
        ev = [(t, n) for t, n in r["events"] if t0 <= t < t1]
        if len(ev) >= 2:
            out.append((ev[-1][0] - ev[0][0]) / (sum(n for _, n in ev) - 1))
    return out


def end_to_end(records: List[Dict], marks: Dict, setup_s: float) -> Dict:
    t0, t1 = marks["t0"], marks["t1"]
    ttft = ttft_samples(records, t0, t1)
    tpot = tpot_samples(records, t0, t1)
    return {
        "tokens_per_s": window_tokens(records, t0, t1) / (t1 - t0),
        "ttft_p90_s": _pct(ttft, 90),
        "tpot_p90_ms": None if not tpot else 1e3 * _pct(tpot, 90),
        "setup_s": setup_s,
    }


def layer_run(records, marks, probe: Probe, trace: Optional[Dict],
              cell: spec.Cell, chips: int, peaks: Optional[Dict]) -> Dict:
    """What the per-layer readers read: the traced part of the window, with
    the configuration and its family (``model``), which counts the work a
    step requires (``bench.flops``)."""
    t0 = marks["t0"]
    t1 = marks.get("t_trace", marks["t1"])
    c0 = marks["counters0"]
    c1 = marks.get("counters_trace", marks["counters1"])
    return {
        "t0": t0, "t1": t1, "config": cell.config, "model": cell.model,
        "chips": chips, "peaks": peaks,
        "num_slots": probe.num_slots, "trace": trace,
        "tokens_per_s": window_tokens(records, t0, t1) / (t1 - t0),
        "tokens_streamed": c1["tokens_streamed_total"]
        - c0["tokens_streamed_total"],
        "ticks": [a for t, a in probe.ticks if t0 <= t < t1],
        "steps": [ctx for t, ctx in probe.steps if t0 <= t < t1],
        "queue_waits": [w for t, w in probe.admits if t0 <= t < t1],
    }


def account(records: List[Dict], marks: Dict, mix: Dict) -> Dict:
    """Each request's own account: sent in the window, failed, cut short
    (done with fewer tokens than asked), or stalled (not done and silent
    for ``stall_s`` when the window closed)."""
    t0, t1 = marks["t0"], marks["t1"]
    stall = mix["stall_s"]
    sent = [r for r in records if r["sent"] is not None and t0 <= r["due"] < t1]
    failed = [r for r in sent if r["error"] is not None]
    short = [r for r in records if r["done"] is not None
             and len(r["tokens"]) != r["max_new"]]
    stalled = [r for r in records
               if r["done"] is None and r["error"] is None
               and r["sent"] is not None and r["sent"] < t1 - stall
               and (r["events"][-1][0] if r["events"] else r["sent"])
               < t1 - stall]
    lateness = [r["sent"] - r["due"] for r in sent]
    return {"attempted": len(sent), "failed": len(failed),
            "short": len(short), "stalled": len(stalled),
            "errors": [r["error"] for r in failed][:3],
            "late_p50_s": _pct(lateness, 50), "late_max_s":
            max(lateness) if lateness else None}


def step_gaps(steps, pauses, t0: float, t1: float,
              n: int = 3) -> List[List[float]]:
    """The ``n`` longest gaps between the starts of consecutive engine steps
    in the window, as [seconds after its start, gap seconds, seconds of the
    gap spent in Python's garbage collector]: where a run that reads low
    lost its time."""
    ts = [t for t, _ in steps if t0 <= t < t1]
    gaps = sorted(((b - a, a) for a, b in zip(ts, ts[1:])), reverse=True)[:n]
    return [[a - t0, gap, sum(max(0.0, min(a + gap, p + d) - max(a, p))
                              for p, d in pauses)]
            for gap, a in gaps]


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             t_proc0: float, require_tpu: bool = True,
             peaks: Optional[Dict] = None, trace_dir: Optional[str] = None,
             warm_timeout: float = WARM_TIMEOUT_S,
             control: bool = False, cache: bool = True) -> Dict:
    """One run; returns the result line's object, with ``checks`` last.
    ``control`` puts the control in the program's place: its gap at the
    same positions (``bench.check``) is what ``served_logit_gap`` checks,
    and the program's own gap goes under ``extra``."""
    import jax

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell.chips):
        raise NoChip(f"cell {cell.name} needs {cell.chips} TPU chip(s); "
                     f"JAX found {len(devices)} {devices[0].platform} "
                     f"device(s)")
    if peaks is None and trace:
        peaks = spec.peaks_for(devices[0].device_kind)
    if cache:
        log(f"compile cache: {enable_compile_cache()}")
    compiles = CompileLog.get()
    c, model, mix = cell.config, cell.model, cell.traffic
    cfg = model.program_config(c)
    check_layout(model, c, cfg)
    mesh = make_mesh(c)
    used = list(mesh.devices.flat) if mesh is not None else devices[:1]

    t = time.monotonic()
    shardings = None
    if mesh is not None:
        from repro.sharding.policy import param_shardings
        shardings = param_shardings(param_structs(model, c), mesh)
    params = make_params(model, c, shardings)
    jax.block_until_ready(params)
    log(f"weights: {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.param_dtype}, drawn in {time.monotonic() - t:.3f} s")
    t = time.monotonic()
    frontend = build_frontend(params, cfg, c, mix, mesh)
    probe = Probe(frontend)
    log(f"engine built in {time.monotonic() - t:.3f} s")

    specs = traffic.generate(mix, seconds, c["vocab_size"])
    marks: Dict = {}
    if trace and trace_dir is None:
        # one trace per checkout: the newest run's, kept for a look by hand
        base = os.path.join(spec.CHECKOUT, ".bench_trace")
        shutil.rmtree(base, ignore_errors=True)
        trace_dir = os.path.join(base, cell.name)
    records = asyncio.run(serve(frontend, mix, specs, seconds,
                                trace_dir if trace else None, marks,
                                warm_timeout))
    setup_s = marks["t0"] - t_proc0
    log(f"set-up {setup_s:.3f} s (warm-up done "
        f"{marks['warm_done'] - t_proc0:.3f} s after start, then "
        f"{mix['preroll_s']} s pre-roll)")
    in_window = compiles.between(marks["t0"], marks["t1"])
    compiles.report()
    log(f"compiles inside the window: {in_window}")
    mem = memory_peak(used)
    acct = account(records, marks, mix)
    log(f"requests: {acct}")
    counters = {k: marks["counters1"][k] - marks["counters0"][k]
                for k in ("engine_steps_total", "engine_admits_total",
                          "tokens_streamed_total", "finished_total",
                          "host_syncs_total", "stream_syncs_total",
                          "rejected_total")}
    log(f"engine counters over the window: {counters}")
    gaps_s = step_gaps(probe.steps, probe.gc_pauses, marks["t0"],
                       marks["t1"])
    log(f"longest gaps between engine steps (s into the window, s, s of "
        f"it collecting garbage): {gaps_s}")

    result: Dict = {"correct": False, "attempted": acct["attempted"],
                    "failed": acct["failed"], "metrics": {}}
    names = [m["name"] for m in (cell.per_layer if trace
                                 else cell.end_to_end)]
    units = {m["name"]: m["unit"] for m in cell.per_layer + cell.end_to_end}
    if trace:
        reduced = trace_reduce.reduce_trace(trace_dir)
        if require_tpu:
            trace_reduce.require_step(reduced)
        run = layer_run(records, marks, probe, reduced, cell, len(used),
                        peaks)
        for name in names:
            value = spec.metric_reader(name)(run)
            if value is not None:
                result["metrics"][name] = {"value": value,
                                           "unit": units[name]}
    else:
        e2e = end_to_end(records, marks, setup_s)
        for name in names:
            if e2e.get(name) is not None:
                result["metrics"][name] = {"value": e2e[name],
                                           "unit": units[name]}
    result["device"] = {"platform": devices[0].platform,
                        "kind": devices[0].device_kind,
                        "count": len(devices), "memory_peak_bytes": mem}
    if trace:
        red = run["trace"] or {}
        result["device"]["busy_s"] = red.get("busy_s", 0.0)
        result["device"]["window_s"] = red.get("window_s", 0.0)
        result["breakdown"] = {"device_ops": red.get("top_ops", []),
                               "idle_gaps": red.get("idle_by_span", [])}

    # the check, once the program's state is freed
    probe_steps = probe.steps
    probe.close()
    del frontend, probe
    gc.collect()
    t = time.monotonic()
    picked = check.sample(records, seed, min_tokens=mix["check_tokens"],
                          max_requests=mix["check_requests"])
    gaps = check.served_gaps(model, params, c, picked, mix["engine"],
                             control=control) if picked else {
        "served_logit_gap": None, "tokens_compared": 0,
        "requests_compared": 0}
    log(f"reference over {gaps['requests_compared']} requests, "
        f"{gaps['tokens_compared']} served tokens, in "
        f"{time.monotonic() - t:.3f} s")
    lim = cell.limits
    # the control is judged in the program's place; the program's own
    # reading of the same run is kept beside it
    gap = gaps["control_logit_gap" if control else "served_logit_gap"]
    checks = {
        "served_logit_gap": (gap,
                             lim["served_logit_gap"]["limit"], "<="),
        "tokens_compared": (gaps["tokens_compared"],
                            lim["tokens_compared"]["limit"], ">="),
        "requests_failed": (acct["failed"], 0, "<="),
        "requests_cut_short": (acct["short"], 0, "<="),
        "requests_stalled": (acct["stalled"], 0, "<="),
    }
    ok = all(v is not None and (v <= lim_ if rule == "<=" else v >= lim_)
             for v, lim_, rule in checks.values())
    result["correct"] = bool(ok)
    result["extra"] = {
        "requests": {k: v for k, v in acct.items() if k != "errors"},
        "counters": counters,
        "queue_depth_end": marks["counters1"]["queue_depth"],
        "compiles_in_window": in_window,
        "step_gaps": gaps_s,
        "mean_accepted": (counters["tokens_streamed_total"]
                          / max(1, sum(len(x) for t, x in probe_steps
                                       if marks["t0"] <= t < marks["t1"]))),
        **({"program_logit_gap": gaps["served_logit_gap"]} if control
           else {})}
    result["checks"] = {k: {"value": v, "limit": lim_, "rule": rule}
                        for k, (v, lim_, rule) in checks.items()}
    return result
