"""Serving throughput: continuous batching vs run-to-completion batching.

Replays the same mixed-length Poisson workload through both serving modes
on the same model:

  * engine — the slot-based continuous-batching engine (repro.serving):
    finished requests are evicted and queued ones admitted mid-flight, so
    a slot never idles while work is waiting.
  * static — run-to-completion ``bpd_decode``: FCFS batches of
    ``num_slots`` requests, each batch held resident until its slowest row
    finishes (per-row budgets via ``max_new_rows``, so rows do stop at
    their own length — the waste is the dead slots, not extra tokens).

Reports aggregate tokens/sec and p50/p95 request latency for both, plus
the engine's jit cache sizes (the recompilation regression guard: admit /
step / evict must each compile exactly once regardless of traffic).

A third, **mixed-policy** row serves a Poisson workload whose requests
carry PER-REQUEST decode policies over per-policy slot groups
({exact, adaptive}), against the BEST of one-per-policy single-policy
baseline runs of the same workload — gated (in --smoke) to within 10% of
that baseline's tokens/sec with exactly one compile per group function;
the row lands in ``BENCH_serve.json`` as the ``mixed_*`` fields.

A fourth, **paged-KV** row reruns the engine workload with
``cache_backend="paged"`` and sweeps slots-vs-HBM via the cache backends'
``memory_bytes``: the ``paged_*`` fields record how many paged slots fit
the dense engine's cache budget (gated >= 4x, worst-case pool with no
prefix-sharing credit) and the equal-slot-count throughput (gated within
10% of dense in --smoke).

Device-work accounting is symmetric: ``model_calls`` counts jitted
forward executions over the full batch width — prefill + decode
iterations per static batch, admits + engine steps for the engine — so
``tokens_per_model_call`` compares the two modes on identical terms
(idle engine slots and dummy static rows both count against their mode).
Per-request k̂ is only reported for the engine, where per-request
iteration counts exist.

Prompts all use ``max_prompt_len`` tokens because the static baseline
conditions on its whole padded prompt buffer; the length mix that matters
for continuous batching is in ``max_new``.

    PYTHONPATH=src python benchmarks/serve_throughput.py [--smoke]

``--smoke`` runs a seconds-scale configuration for CI (correctness and
compile-count checks, not a performance measurement).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import DecodeConfig, ModelConfig
from repro.models import model as M
from repro.serving import (
    ContinuousBatchingEngine,
    DecodeSession,
    EngineConfig,
    Request,
    Scheduler,
    aggregate_stats,
    tracing,
)
from repro.serving.types import percentile

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def bench_model(smoke: bool) -> ModelConfig:
    if smoke:
        return ModelConfig(name="serve-smoke", num_layers=2, d_model=64,
                           num_heads=4, num_kv_heads=2, d_ff=128,
                           vocab_size=97, bpd_k=4, max_seq_len=512,
                           dtype="float32")
    return ModelConfig(name="serve-bench", num_layers=4, d_model=256,
                       num_heads=8, num_kv_heads=4, d_ff=512,
                       vocab_size=512, bpd_k=8, max_seq_len=2048,
                       dtype="float32")


def make_workload(rng, n: int, rate: float, prompt_len: int, vocab: int,
                  budgets) -> list:
    """n requests, Poisson arrivals at ``rate`` req/s, max_new drawn from
    ``budgets`` (the mixed-length aspect that static batching wastes on)."""
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n))
    return [Request(rid=i,
                    prompt=rng.integers(0, vocab, size=prompt_len),
                    max_new=int(rng.choice(budgets)),
                    arrival=float(arrivals[i]))
            for i in range(n)]


# ---------------------------------------------------------------------------
# Continuous-batching engine
# ---------------------------------------------------------------------------


VT_DT = 1e-3
"""Virtual seconds per full-width model forward.

Both serving drivers replay arrivals in VIRTUAL time: the clock advances
``VT_DT`` per model forward (engine decode iterations + prefill batches,
static's fused-loop invocations) and jumps to the next arrival when
idle, instead of sleeping on the host clock.  Admission interleaving —
which requests share a batch, when slots refill — is then a function of
the workload alone, so the structural gate numbers (model calls, tokens
per call) are reproducible run-over-run; wall time only measures the
back-to-back device work, with no sleep jitter inside the window."""


def run_engine(params, cfg, dec, ecfg, reqs, *, policies=None, reps=1):
    """Drive ``reqs`` through the engine.  ``policies`` ({name: slots})
    switches on per-request decode policies: the engine partitions its
    slots into per-policy groups and each request is served by the group
    running its ``Request.policy``.

    Arrivals replay in virtual time (see ``VT_DT``), so the gated stats
    are deterministic; ``reps`` (same engine, fresh scheduler — no
    recompilation) survives for host-wall investigations and keeps the
    best replicate by tokens/sec."""
    eng = ContinuousBatchingEngine(params, cfg, dec, ecfg, policies=policies)
    # warm-up: compile every group's admit/step/evict outside the measured
    # window (one tiny request per policy group)
    warm = Scheduler(eng)
    for i, name in enumerate(eng.policy_names()):
        warm.submit(Request(rid=-1 - i, policy=name,
                            prompt=np.zeros(ecfg.max_prompt_len, np.int32),
                            max_new=2))
    warm.run()

    best = None
    for _ in range(reps):
        sched = Scheduler(eng)
        admits0, steps0 = eng.num_admits, eng.num_steps  # this rep only
        pre0, ov0 = eng.num_prefill_batches, eng.num_overlap_harvests
        bp0 = eng.num_attach_backpressure
        # host time per phase: the engine's span totals over this rep
        spans0 = tracing.totals()
        for r in reqs:                  # copies: reps stay isolated
            sched.submit(dataclasses.replace(r))

        def work():                     # model forwards so far this rep
            return eng.num_steps + (eng.num_prefill_batches
                                    if eng.disaggregated else eng.num_admits)

        vt, w_prev, ticks = 0.0, work(), 0
        t0 = time.monotonic()
        while not sched.drained():
            ticks += 1
            if ticks > 1_000_000:
                raise RuntimeError("virtual-time serving loop did not drain")
            if (not eng.has_active() and not sched.pending(vt)
                    and eng.handoff_backlog() == 0):
                vt = min(r.arrival for r in sched.queue)  # idle: next arrival
                continue
            sched.step(now=vt)
            w_now = work()
            vt += (w_now - w_prev) * VT_DT
            w_prev = w_now
        host_wall = time.monotonic() - t0
        finished = sched.finished
        # throughput in VIRTUAL time: tokens per unit of simulated device
        # time (forwards x VT_DT + arrival idle) — deterministic given the
        # workload, so the speedup gates measure scheduling structure, not
        # host dispatch jitter; the host wall rides along as its own row
        stats = aggregate_stats(finished, vt)
        stats["host_wall_seconds"] = host_wall
        # device-work accounting: unified admits are one forward each;
        # disaggregated admission costs one forward per PREFILL BATCH
        # (attach is a scatter, not a forward) — the batching is the
        # speedup
        prefills = ((eng.num_prefill_batches - pre0) if eng.disaggregated
                    else (eng.num_admits - admits0))
        stats["model_calls"] = prefills + (eng.num_steps - steps0)
        # per-phase host-time attribution: where the serving loop's wall
        # time actually went (the ledger behind the speedup gates)
        stats["span_seconds"] = {
            name: t["seconds_total"]
            - spans0.get(name, {}).get("seconds_total", 0.0)
            for name, t in sorted(tracing.totals().items())}
        stats["overlap_harvests"] = eng.num_overlap_harvests - ov0
        if eng.disaggregated:
            stats["prefill_batches"] = eng.num_prefill_batches - pre0
            stats["attach_backpressure"] = eng.num_attach_backpressure - bp0
        stats["tokens_per_model_call"] = (stats["total_tokens"]
                                          / max(stats["model_calls"], 1))
        if policies:
            stats["policy_groups"] = dict(policies)
            stats["per_policy_tokens"] = {
                n: sum(f.generated for f in finished if f.policy == n)
                for n in eng.policy_names()}
        if best is None or stats["tokens_per_sec"] > best["tokens_per_sec"]:
            best = stats
    # cache sizes AFTER every replicate: a recompile in any rep still trips
    best["compile_counts"] = eng.compile_counts()
    return best


# ---------------------------------------------------------------------------
# Static run-to-completion baseline
# ---------------------------------------------------------------------------


def run_static(params, cfg, dec, ecfg, reqs, *, reps=1):
    """FCFS batches of num_slots through the run-to-completion decode path
    (a jitted DecodeSession — the same driver the engine runs on); a batch's
    requests all complete when its slowest row does.  ``reps`` keeps the
    best replicate, symmetric with ``run_engine``."""
    s = ecfg.num_slots
    sess = DecodeSession(params, cfg, dec, jit=True)
    decode = lambda batch, budgets: sess.decode(batch,  # noqa: E731
                                               max_new_rows=budgets)

    dummy = {"tokens": jnp.zeros((s, ecfg.max_prompt_len), jnp.int32)}
    jax.block_until_ready(decode(dummy, jnp.ones((s,), jnp.int32)))  # compile

    best = None
    for _ in range(reps):
        queue = sorted(reqs, key=lambda r: r.arrival)
        total_tokens = 0
        model_calls = 0
        latencies = []
        vt = 0.0                        # same virtual clock as run_engine
        t0 = time.monotonic()
        while queue:
            if queue[0].arrival > vt:
                vt = queue[0].arrival   # idle until the next arrival
            take = [r for r in queue if r.arrival <= vt][:s]
            queue = [r for r in queue if r not in take]
            prompts = np.zeros((s, ecfg.max_prompt_len), np.int32)
            budgets = np.ones((s,), np.int32)      # dummy rows: 1 token
            for i, r in enumerate(take):
                prompts[i] = r.prompt
                budgets[i] = min(r.max_new, ecfg.max_new_cap)
            _, st = decode({"tokens": jnp.asarray(prompts)},
                           jnp.asarray(budgets))
            jax.block_until_ready(st["generated"])
            gen = np.asarray(st["generated"])
            inv = int(st["invocations"])            # prefill + iterations
            model_calls += inv
            vt += inv * VT_DT           # the batch ran to completion
            for i, r in enumerate(take):
                total_tokens += int(gen[i])
                latencies.append(vt - r.arrival)
        host_wall = time.monotonic() - t0
        stats = {
            "requests": len(reqs),
            "total_tokens": total_tokens,
            "model_calls": model_calls,
            "tokens_per_model_call": total_tokens / max(model_calls, 1),
            "tokens_per_sec": total_tokens / vt if vt else 0.0,
            "latency_p50_s": percentile(latencies, 50),
            "latency_p95_s": percentile(latencies, 95),
            "wall_seconds": vt,
            "host_wall_seconds": host_wall,
        }
        if best is None or stats["tokens_per_sec"] > best["tokens_per_sec"]:
            best = stats
    return best


# ---------------------------------------------------------------------------


def run(smoke: bool = False, requests: int = 48, slots: int = 8,
        rate: float = 100.0, seed: int = 0) -> dict:
    cfg = bench_model(smoke)
    if smoke:
        # arrivals overlapping service (rate ~ service rate): continuous
        # batching's edge is mid-flight admission into freed slots while
        # run-to-completion serializes at batch boundaries — a pure burst
        # would instead reward static's fully-fused decode loop, and an
        # arrival-starved trace collapses every ratio to 1.0.  Width 8 is
        # the smallest batch where static's padding waste (short rows
        # riding a full-width fused loop) outweighs the engine's per-step
        # dispatch overhead on the host backend.
        requests, slots, rate = min(requests, 32), min(slots, 8), 200.0
    dec = DecodeConfig(max_new_tokens=0, block_k=cfg.bpd_k)
    # steps_per_sync=4: every serving-engine run below uses windowed
    # decode — up to 4 fused iterations per dispatch with early exit the
    # moment a row finishes — so the engine keeps continuous batching's
    # slot-refill timing while approaching static's fused-loop dispatch
    # economy (tokens stay bitwise identical; see tests/test_disagg.py)
    ecfg = EngineConfig(num_slots=slots,
                        max_prompt_len=8 if smoke else 16,
                        max_new_cap=16 if smoke else 64,
                        steps_per_sync=4)
    dec = dec.replace(max_new_tokens=ecfg.max_new_cap)
    budgets = (2, 16) if smoke else (4, 16, 64)
    rng = np.random.default_rng(seed)
    reqs = make_workload(rng, requests, rate, ecfg.max_prompt_len,
                         cfg.vocab_size, budgets)
    params = M.init(jax.random.PRNGKey(seed), cfg)
    # virtual-time replay makes every gated ratio deterministic given the
    # workload, so one replicate per mode suffices (reps survives as a
    # knob for host-wall investigations)
    reps = 1

    engine_stats = run_engine(params, cfg, dec, ecfg, reqs, reps=reps)
    static_stats = run_static(params, cfg, dec, ecfg, reqs, reps=reps)

    # mixed-policy row: a Poisson workload with a PER-REQUEST decode policy
    # served by per-policy slot groups, against its own single-policy
    # baseline run of the SAME workload.  Two deliberate choices keep this
    # a measurement of the serving stack rather than of workload shape:
    #
    #   * each group is sized at the baseline's slot width, so every group
    #     step has the IDENTICAL geometry as the baseline step — the
    #     comparison isolates the grouping machinery (per-group compiled
    #     steps, round-robin dispatch, one fused sync per group step) from
    #     small-batch matmul efficiency, a hardware property;
    #   * the workload is long enough that the steady packed phase
    #     dominates each group's drain tail (the last long request
    #     decoding alone), which with a handful of requests would measure
    #     workload fragmentation instead.
    #
    # Policy heterogeneity is a scheduling change, not a decoding change,
    # so tokens/sec must stay within 10% of the best single-policy run
    # (gated in main) with zero per-step recompilation after warmup.
    mixed_n = max(requests, 64) if smoke else requests
    mreqs = make_workload(rng, mixed_n, rate, ecfg.max_prompt_len,
                          cfg.vocab_size, budgets)

    groups = {"exact": slots, "adaptive": slots}
    names = list(groups)
    # "best single-policy run": one baseline per constituent policy on the
    # same workload (every request forced to that one policy), best taken
    # by tokens/sec — the mixed run is gated against the winner
    base_runs = {}
    for name in names:
        base_reqs = [dataclasses.replace(r, policy=name) for r in mreqs]
        base_runs[name] = run_engine(params, cfg, dec, ecfg, base_reqs,
                                     policies={name: slots}, reps=reps)
    best_name = max(base_runs, key=lambda n: base_runs[n]["tokens_per_sec"])
    single_base_stats = base_runs[best_name]
    mixed_ecfg = dataclasses.replace(ecfg, num_slots=sum(groups.values()))
    # round-robin within each budget class so both groups carry the same
    # length mix (an index round-robin can hand one group most of the long
    # requests, and its drain tail would be charged to the serving stack)
    order = sorted(range(len(mreqs)), key=lambda i: (mreqs[i].max_new, i))
    pol_of = {i: names[j % len(names)] for j, i in enumerate(order)}
    mixed_reqs = [dataclasses.replace(r, policy=pol_of[i])
                  for i, r in enumerate(mreqs)]
    mixed_stats = run_engine(params, cfg, dec, mixed_ecfg, mixed_reqs,
                             policies=groups, reps=reps)

    # paged KV cache rows: the memory claim (how many concurrent slots fit
    # in the dense engine's HBM budget) and the throughput claim (paged is
    # not slower at the same slot count — it is a layout change, not a
    # compute change).  Memory is measured with the backends' own
    # ``memory_bytes`` (eval_shape over the real init, so block tables,
    # position maps and the trash page are all accounted), with the paged
    # pool at its worst case (no prefix sharing: every slot holds its full
    # page span).
    from repro.models import cache as cache_lib

    decp = dec.replace(cache_backend="paged", page_size=8)
    prefix = cfg.num_meta_tokens
    context_len = prefix + ecfg.max_prompt_len + ecfg.max_new_cap
    P = cache_lib.pages_per_row(context_len, dec.block_k, decp.page_size)

    def _dense_bytes(s):
        return cache_lib.DenseBackend().memory_bytes(
            cfg, s, context_len, dec.block_k)

    def _paged_bytes(s):
        be = cache_lib.PagedBackend(decp.page_size, num_pages=1 + s * P,
                                    managed=True)
        return be.memory_bytes(cfg, s, context_len, dec.block_k)

    hbm_budget = _dense_bytes(slots)
    paged_slots = slots
    while (paged_slots < 64 * slots
           and _paged_bytes(paged_slots + 1) <= hbm_budget):
        paged_slots += 1
    paged_stats = run_engine(params, cfg, decp, ecfg, reqs, reps=reps)

    # disaggregated prefill/decode rows: the same decode-slot geometry
    # with dedicated prefill workers feeding decode through the KV-handoff
    # queue, sized at a 1:2 prefill:decode ratio — prompts are short
    # relative to decode budgets, so half-width workers stay saturated
    # while halving the padding waste of partial prefill batches (the
    # padded worker forward always computes prefill_slots rows).  Two
    # comparisons:
    #   * the base trace, engine/disagg vs static — the serving stack must
    #     BEAT run-to-completion batching on CALL ECONOMY (tokens per
    #     full-width model forward, >= 1.05x/1.15x gates in main): batched
    #     worker prefills amortize the per-admission forward that made
    #     the unified engine lose its 0.95x smoke round, and the windowed
    #     step counts every iteration it ran so the accounting stays
    #     symmetric with static's fused loop;
    #   * an admission-heavy Poisson trace (short budgets, so prefill
    #     dominates decode), disagg vs the UNIFIED engine at equal device
    #     count — the >= 1.15x gate on the disaggregation win itself.
    disagg_ecfg = dataclasses.replace(ecfg,
                                      prefill_slots=max(slots // 2, 2))
    disagg_stats = run_engine(params, cfg, dec, disagg_ecfg, reqs,
                              reps=reps)
    disagg_n = max(requests, 32) if smoke else requests
    disagg_budgets = (2, 4) if smoke else (4, 8)
    # near-simultaneous arrivals: the disaggregation claim is about the
    # ADMISSION path (batched worker prefills vs one forward per admit),
    # so the trace must keep admission busy rather than arrival-starved
    disagg_rate = 2000.0 if smoke else rate
    dreqs = make_workload(rng, disagg_n, disagg_rate, ecfg.max_prompt_len,
                          cfg.vocab_size, disagg_budgets)
    disagg_trace_unified = run_engine(params, cfg, dec, ecfg, dreqs,
                                      reps=reps)
    disagg_trace_stats = run_engine(params, cfg, dec, disagg_ecfg, dreqs,
                                    reps=reps)

    return {
        "config": {"requests": requests, "slots": slots, "rate": rate,
                   "budgets": list(budgets), "model": cfg.name,
                   "smoke": smoke, "mixed_groups": groups,
                   "mixed_requests": mixed_n,
                   "page_size": decp.page_size, "pages_per_row": P},
        "engine": engine_stats,
        "paged": paged_stats,
        "paged_slots_at_equal_hbm": paged_slots,
        "paged_slots_ratio": paged_slots / slots,
        "dense_cache_bytes": hbm_budget,
        "dense_cache_bytes_per_slot": hbm_budget / slots,
        "paged_cache_bytes_at_equal_slots": _paged_bytes(slots),
        "paged_vs_dense_tokens_per_sec": (
            paged_stats["tokens_per_sec"]
            / max(engine_stats["tokens_per_sec"], 1e-9)),
        "static": static_stats,
        "single_base": single_base_stats,
        "single_base_policy": best_name,
        "single_base_all": {n: s["tokens_per_sec"]
                            for n, s in base_runs.items()},
        "mixed": mixed_stats,
        "disagg": disagg_stats,
        "disagg_trace": disagg_trace_stats,
        "disagg_trace_unified": disagg_trace_unified,
        "disagg_tokens_per_sec": disagg_stats["tokens_per_sec"],
        "disagg_vs_engine_tokens_per_sec": (
            disagg_trace_stats["tokens_per_sec"]
            / max(disagg_trace_unified["tokens_per_sec"], 1e-9)),
        "disagg_speedup_tokens_per_sec": (
            disagg_stats["tokens_per_sec"]
            / max(static_stats["tokens_per_sec"], 1e-9)),
        "speedup_tokens_per_sec": (engine_stats["tokens_per_sec"]
                                   / max(static_stats["tokens_per_sec"],
                                         1e-9)),
        "mixed_vs_best_single": (mixed_stats["tokens_per_sec"]
                                 / max(single_base_stats["tokens_per_sec"],
                                       1e-9)),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale CI run (correctness + compile "
                         "counts, not a perf measurement)")
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--rate", type=float, default=100.0,
                    help="Poisson arrival rate, requests/sec")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    res = run(smoke=args.smoke, requests=args.requests, slots=args.slots,
              rate=args.rate, seed=args.seed)

    for mode in ("engine", "static", "mixed", "disagg"):
        st = res[mode]
        for key in ("tokens_per_sec", "latency_p50_s", "latency_p95_s",
                    "model_calls", "tokens_per_model_call", "wall_seconds"):
            print(f"serve/{mode}/{key},{st[key]},", flush=True)
    print(f"serve/engine/mean_accepted,{res['engine']['mean_accepted']},"
          f"per_request_khat")
    print(f"serve/speedup_tokens_per_sec,{res['speedup_tokens_per_sec']:.3f},"
          f"engine_vs_static")
    # per-phase host-time attribution of the engine-vs-static gap: the
    # unified engine pays one prefill FORWARD per admission; disaggregation
    # batches those into prefill-worker forwards
    for mode in ("engine", "disagg"):
        for name, sec in res[mode]["span_seconds"].items():
            print(f"serve/{mode}/{name},{sec:.4f},s")
    print(f"serve/disagg/prefill_batches,{res['disagg']['prefill_batches']},"
          f"requests={res['config']['requests']}")
    print(f"serve/disagg/overlap_harvests,"
          f"{res['disagg']['overlap_harvests']},")
    print(f"serve/disagg_speedup_tokens_per_sec,"
          f"{res['disagg_speedup_tokens_per_sec']:.3f},disagg_vs_static")
    print(f"serve/disagg_vs_engine_tokens_per_sec,"
          f"{res['disagg_vs_engine_tokens_per_sec']:.3f},"
          f"admission_heavy_trace_equal_devices")
    print(f"serve/mixed_vs_best_single,{res['mixed_vs_best_single']:.3f},"
          f"mixed_policy_groups={res['config']['mixed_groups']}_vs_"
          f"{res['single_base_policy']}")

    cc = res["engine"]["compile_counts"]
    if any(v != 1 for v in cc.values()):
        raise SystemExit(f"RECOMPILATION REGRESSION: engine jit cache sizes "
                         f"{cc} (expected 1 each)")
    print(f"serve/engine/compile_counts,{cc},ok")

    # per-request-policy gates: every group's admit/step/evict compiled
    # exactly once across the whole trafficked run (no per-step
    # recompilation after warmup), and policy slot grouping costs at most
    # 10% tokens/sec against the best single-policy run
    mcc = res["mixed"]["compile_counts"]
    if any(v != 1 for v in mcc.values()):
        raise SystemExit(f"RECOMPILATION REGRESSION (mixed-policy): engine "
                         f"jit cache sizes {mcc} (expected 1 each)")
    print(f"serve/mixed/compile_counts,{mcc},ok")

    # paged-KV gates: the layout must buy >= 4x the concurrent slots inside
    # the dense engine's HBM budget (worst-case pool, no prefix sharing
    # credited), serve the same workload within 10% of dense tokens/sec at
    # equal slot count, and never recompile under traffic
    print(f"serve/paged/tokens_per_sec,{res['paged']['tokens_per_sec']},")
    print(f"serve/paged_slots_at_equal_hbm,{res['paged_slots_at_equal_hbm']},"
          f"dense_slots={res['config']['slots']}")
    print(f"serve/paged_vs_dense_tokens_per_sec,"
          f"{res['paged_vs_dense_tokens_per_sec']:.3f},equal_slot_count")
    pcc = res["paged"]["compile_counts"]
    if any(v != 1 for v in pcc.values()):
        raise SystemExit(f"RECOMPILATION REGRESSION (paged): engine jit "
                         f"cache sizes {pcc} (expected 1 each)")
    print(f"serve/paged/compile_counts,{pcc},ok")
    if res["paged_slots_ratio"] < 4.0:
        raise SystemExit(
            f"PAGED MEMORY REGRESSION: only {res['paged_slots_at_equal_hbm']}"
            f" paged slots fit the dense {res['config']['slots']}-slot HBM "
            f"budget ({res['paged_slots_ratio']:.2f}x, need >= 4x): "
            f"{res['dense_cache_bytes_per_slot']:.0f} B/slot dense vs "
            f"{res['paged_cache_bytes_at_equal_slots'] / res['config']['slots']:.0f}"
            f" B/slot paged")
    if args.smoke and res["paged_vs_dense_tokens_per_sec"] < 0.9:
        raise SystemExit(
            f"PAGED THROUGHPUT REGRESSION: "
            f"{res['paged']['tokens_per_sec']:.1f} tok/s is "
            f"{res['paged_vs_dense_tokens_per_sec']:.2f}x dense "
            f"({res['engine']['tokens_per_sec']:.1f} tok/s) on the same "
            f"workload at equal slot count; the paged layout must cost "
            f"< 10%")

    if args.smoke and res["mixed_vs_best_single"] < 0.9:
        raise SystemExit(
            f"MIXED-POLICY THROUGHPUT REGRESSION: "
            f"{res['mixed']['tokens_per_sec']:.1f} tok/s is "
            f"{res['mixed_vs_best_single']:.2f}x the best single-policy "
            f"run ({res['single_base_policy']}: "
            f"{res['single_base']['tokens_per_sec']:.1f} tok/s on the "
            f"same workload); per-request policies must cost < 10%")

    # disaggregation gates: engine jit caches stay compile-once, the
    # disaggregated engine beats the unified one >= 1.15x on the
    # admission-heavy Poisson trace at equal device count, and the serving
    # stack's best mode now beats run-to-completion static batching
    # (the historical engine<static smoke regression, attributed above by
    # the per-phase timers to per-admission prefill dispatch)
    dcc = res["disagg"]["compile_counts"]
    if any(v != 1 for v in dcc.values()):
        raise SystemExit(f"RECOMPILATION REGRESSION (disagg): engine jit "
                         f"cache sizes {dcc} (expected 1 each)")
    print(f"serve/disagg/compile_counts,{dcc},ok")
    if args.smoke and res["disagg_vs_engine_tokens_per_sec"] < 1.15:
        raise SystemExit(
            f"DISAGGREGATION REGRESSION: "
            f"{res['disagg_trace']['tokens_per_sec']:.1f} tok/s is only "
            f"{res['disagg_vs_engine_tokens_per_sec']:.2f}x the unified "
            f"engine ({res['disagg_trace_unified']['tokens_per_sec']:.1f} "
            f"tok/s) on the admission-heavy Poisson trace at equal device "
            f"count; prefill/decode disaggregation must buy >= 1.15x")
    # engine-vs-static on the base trace.  Virtual-time replay makes both
    # the throughput ratios (tokens per unit of simulated device time)
    # and the call-economy ratios (tokens per full-width model forward,
    # with the windowed step counting every iteration it ran) fully
    # deterministic given the workload — identical runs print identical
    # numbers — so these gate exactly, with no wall-clock noise margin.
    e_tpmc = (res["engine"]["tokens_per_model_call"]
              / max(res["static"]["tokens_per_model_call"], 1e-9))
    d_tpmc = (res["disagg"]["tokens_per_model_call"]
              / max(res["static"]["tokens_per_model_call"], 1e-9))
    print(f"serve/engine_vs_static_tokens_per_model_call,{e_tpmc:.3f},"
          f"call_economy")
    print(f"serve/disagg_vs_static_tokens_per_model_call,{d_tpmc:.3f},"
          f"call_economy")
    if args.smoke and e_tpmc < 1.05:
        raise SystemExit(
            f"SERVING CALL-ECONOMY REGRESSION: engine commits "
            f"{res['engine']['tokens_per_model_call']:.2f} tokens per "
            f"model forward vs static {res['static']['tokens_per_model_call']:.2f} "
            f"({e_tpmc:.2f}x): continuous batching must waste fewer "
            f"full-width forwards than run-to-completion padding (>= 1.05x)")
    if args.smoke and d_tpmc < 1.10:
        raise SystemExit(
            f"SERVING CALL-ECONOMY REGRESSION (disagg): "
            f"{res['disagg']['tokens_per_model_call']:.2f} tokens per model "
            f"forward vs static {res['static']['tokens_per_model_call']:.2f} "
            f"({d_tpmc:.2f}x): batched worker prefills must keep the "
            f"engine's call economy past 1.10x static")
    if args.smoke and res["disagg_speedup_tokens_per_sec"] < 1.0:
        raise SystemExit(
            f"SERVING SPEEDUP REGRESSION: disaggregated engine "
            f"{res['disagg']['tokens_per_sec']:.1f} tok/s vs static "
            f"{res['static']['tokens_per_sec']:.1f} tok/s "
            f"({res['disagg_speedup_tokens_per_sec']:.2f}x): continuous "
            f"batching with disaggregated prefill must beat "
            f"run-to-completion batching (>= 1.0x)")
    if args.smoke and res["speedup_tokens_per_sec"] < 1.0:
        raise SystemExit(
            f"SERVING SPEEDUP REGRESSION: unified engine "
            f"{res['engine']['tokens_per_sec']:.1f} tok/s vs static "
            f"{res['static']['tokens_per_sec']:.1f} tok/s "
            f"({res['speedup_tokens_per_sec']:.2f}x): continuous batching "
            f"must beat run-to-completion batching (>= 1.0x)")

    os.makedirs("experiments", exist_ok=True)
    # smoke runs get their own artifact so a CI-sized run never clobbers
    # saved full-benchmark numbers
    name = "serve_throughput_smoke" if args.smoke else "serve_throughput"
    with open(f"experiments/{name}.json", "w") as f:
        json.dump(res, f, indent=2, default=str)

    # repo-root perf-trajectory artifact (tracked in git so every PR's smoke
    # run appends to the history via the diff); full runs keep their own
    # experiments/ record and never clobber the committed smoke baseline
    if not args.smoke:
        return
    bench = {
        "smoke": args.smoke,
        "engine_tokens_per_sec": res["engine"]["tokens_per_sec"],
        "static_tokens_per_sec": res["static"]["tokens_per_sec"],
        "speedup_tokens_per_sec": res["speedup_tokens_per_sec"],
        "engine_tokens_per_model_call": res["engine"]["tokens_per_model_call"],
        "static_tokens_per_model_call": res["static"]["tokens_per_model_call"],
        "engine_mean_accepted": res["engine"]["mean_accepted"],
        "compile_counts": cc,
        "mixed_tokens_per_sec": res["mixed"]["tokens_per_sec"],
        "mixed_vs_best_single": res["mixed_vs_best_single"],
        "best_single_policy": res["single_base_policy"],
        "single_policy_tokens_per_sec": res["single_base_all"],
        "mixed_policy_groups": res["config"]["mixed_groups"],
        "mixed_per_policy_tokens": res["mixed"]["per_policy_tokens"],
        "mixed_compile_counts": mcc,
        "paged_tokens_per_sec": res["paged"]["tokens_per_sec"],
        "paged_vs_dense_tokens_per_sec": res["paged_vs_dense_tokens_per_sec"],
        "paged_slots_at_equal_hbm": res["paged_slots_at_equal_hbm"],
        "paged_slots_ratio": res["paged_slots_ratio"],
        "paged_dense_cache_bytes_per_slot": res["dense_cache_bytes_per_slot"],
        "paged_cache_bytes_at_equal_slots":
            res["paged_cache_bytes_at_equal_slots"],
        "paged_compile_counts": pcc,
        "disagg_tokens_per_sec": res["disagg_tokens_per_sec"],
        "disagg_tokens_per_model_call":
            res["disagg"]["tokens_per_model_call"],
        "engine_vs_static_tokens_per_model_call": e_tpmc,
        "disagg_vs_static_tokens_per_model_call": d_tpmc,
        "disagg_speedup_tokens_per_sec": res["disagg_speedup_tokens_per_sec"],
        "disagg_vs_engine_tokens_per_sec":
            res["disagg_vs_engine_tokens_per_sec"],
        "disagg_prefill_batches": res["disagg"]["prefill_batches"],
        "disagg_overlap_harvests": res["disagg"]["overlap_harvests"],
        "disagg_compile_counts": dcc,
        "engine_span_seconds": res["engine"]["span_seconds"],
        "disagg_span_seconds": res["disagg"]["span_seconds"],
        "config": res["config"],
    }
    # merge-write: BENCH_serve.json is shared with slo_harness.py (the
    # slo_* keys) — each benchmark owns its keys and must not clobber the
    # other's rows
    path = os.path.join(REPO_ROOT, "BENCH_serve.json")
    merged = {}
    if os.path.exists(path):
        with open(path) as f:
            merged = json.load(f)
    merged.update(bench)
    with open(path, "w") as f:
        json.dump(merged, f, indent=2, default=str)


if __name__ == "__main__":
    main()
