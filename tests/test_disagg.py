"""Disaggregated prefill/decode: token identity under ANY interleaving.

The contract under test: a disaggregated engine (dedicated prefill
workers batching prompts, bounded KV-handoff queue, attach into freed
decode slots) produces per-request token streams bitwise identical to the
unified engine — for every interleaving of prefill-completion and
decode-admission orders the host could produce, including handoff-queue-
full back-pressure and page-pool attach stalls.  Identity holds by
construction (``admit ≡ attach ∘ prefill`` at width 1, and batch-size
invariance makes width-W worker batches safe); these tests check the
construction empirically, plus the async-stream observables: phase
timers, ``num_overlap_harvests``, and the one-fused-sync-per-group-step
accounting that PR 5 pinned.
"""
import dataclasses

import jax
import numpy as np
import pytest

from _hyp import given, settings, st
from conftest import tiny_dense
from repro.config import DecodeConfig
from repro.models import model as M
from repro.serving import (
    ContinuousBatchingEngine,
    EngineConfig,
    Request,
    Scheduler,
    tracing,
)

pytestmark = pytest.mark.serving


@pytest.fixture(scope="module")
def stack():
    cfg = tiny_dense()
    params = M.init(jax.random.PRNGKey(0), cfg)
    dec = DecodeConfig(max_new_tokens=10, block_k=4)
    return cfg, params, dec


ECFG = EngineConfig(num_slots=2, max_prompt_len=6, max_new_cap=10,
                    prefill_slots=2, handoff_cap=3)


@pytest.fixture(scope="module")
def disagg(stack):
    cfg, params, dec = stack
    return ContinuousBatchingEngine(params, cfg, dec, ECFG)


@pytest.fixture(scope="module")
def unified(stack):
    cfg, params, dec = stack
    ecfg = dataclasses.replace(ECFG, prefill_slots=0, handoff_cap=0)
    return ContinuousBatchingEngine(params, cfg, dec, ecfg)


def _workload(cfg, seed, n=6):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, arrival=0.0,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=int(rng.integers(2, 7))),
                    max_new=int(rng.integers(3, 11)))
            for i in range(n)]


def _drive_unified(eng, reqs):
    """Simple greedy unified run — admission order does not move tokens
    (the commit stream is a deterministic function of the prompt), so any
    one unified run is THE reference for every disagg interleaving."""
    todo, done = list(reqs), []
    while todo or eng.has_active():
        while todo and eng.free_slots():
            eng.admit(todo.pop(0))
        done += eng.step()
    return {f.rid: f for f in done}


_REF = {}   # workload seed -> unified reference streams


def _reference(unified_eng, cfg, seed):
    if seed not in _REF:
        _REF[seed] = _drive_unified(unified_eng, _workload(cfg, seed))
    return _REF[seed]


def _check_identical(done, ref):
    assert sorted(f.rid for f in done) == sorted(ref)
    for f in done:
        r = ref[f.rid]
        np.testing.assert_array_equal(
            f.tokens, r.tokens,
            err_msg=f"rid={f.rid}: disagg stream diverged from unified")
        assert f.generated == r.generated, f.rid
        assert f.invocations == r.invocations, f.rid


# ---------------------------------------------------------------------------
# Property: every interleaving of stage/prefill/attach/step is identical
# ---------------------------------------------------------------------------


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 2**16),
       ops=st.lists(st.sampled_from("qqpas"), min_size=4, max_size=40))
def test_any_interleaving_token_identical(stack, disagg, unified, seed, ops):
    """Hypothesis drives the disaggregated engine through an ARBITRARY
    op sequence — (q)ueue into the handoff, run worker (p)refills,
    (a)ttach parked rows, decode (s)tep — then drains.  Whatever order
    prefill completions and decode admissions land in (including ops that
    bounce off the full handoff queue), every request's stream matches
    the unified engine bitwise."""
    cfg, _, _ = stack
    reqs = _workload(cfg, seed)
    todo, done, full_bounces = list(reqs), [], 0
    for op in ops:
        if op == "q" and todo:
            if disagg.handoff_free() <= 0:
                # the bounded queue rejects instead of growing without
                # limit — the op is a no-op and the request waits
                with pytest.raises(RuntimeError, match="handoff"):
                    disagg.queue_prefill(todo[0])
                full_bounces += 1
            else:
                disagg.queue_prefill(todo.pop(0))
        elif op == "p":
            disagg.run_prefills()
        elif op == "a":
            disagg.attach_ready()
        elif op == "s" and disagg.has_active():
            done += disagg.step()
    # drain whatever the random schedule left behind
    while todo or disagg.handoff_backlog() or disagg.has_active():
        while todo and disagg.handoff_free() > 0:
            disagg.queue_prefill(todo.pop(0))
        disagg.run_prefills()
        disagg.attach_ready()
        if disagg.has_active():
            done += disagg.step()
    _check_identical(done, _reference(unified, cfg, seed))
    # the module-scoped engine is reused across examples: geometry never
    # changes, so nothing may ever recompile
    assert all(v == 1 for v in disagg.compile_counts().values()), \
        disagg.compile_counts()


# ---------------------------------------------------------------------------
# Deterministic edges: back-pressure on both bounds
# ---------------------------------------------------------------------------


def test_handoff_queue_full_rejects(stack, disagg):
    """``handoff_cap`` bounds staged + parked together; the overflow
    submission raises instead of queueing unboundedly."""
    cfg, _, _ = stack
    reqs = _workload(cfg, seed=99, n=ECFG.handoff_cap + 1)
    for r in reqs[:-1]:
        disagg.queue_prefill(r)
    assert disagg.handoff_free() == 0
    with pytest.raises(RuntimeError, match="handoff"):
        disagg.queue_prefill(reqs[-1])
    # prefilling moves records staged -> parked without freeing capacity
    disagg.run_prefills()
    assert disagg.handoff_free() == 0
    with pytest.raises(RuntimeError, match="handoff"):
        disagg.queue_prefill(reqs[-1])
    # attaching + draining frees it again
    disagg.attach_ready()
    while disagg.handoff_backlog() or disagg.has_active():
        disagg.run_prefills()
        disagg.attach_ready()
        if disagg.has_active():
            disagg.step()
    assert disagg.handoff_free() == ECFG.handoff_cap


def test_attach_backpressure_page_pool(stack):
    """When the paged KV pool cannot cover the head-of-queue record at
    attach time, the record WAITS at the head (num_attach_backpressure
    counts the stall) and attaches once the in-flight request retires and
    releases its pages — still token-identical to the unified run."""
    cfg, params, dec = stack
    decp = dec.replace(cache_backend="paged", page_size=8)
    # each request spans 2 pages (prompt 4 + budget 6 + lookahead 4 over
    # size-8 pages); pool = 1 trash + 3 allocatable, so ONE admitted
    # request fits but two cannot coexist
    ecfg = dataclasses.replace(ECFG, page_pool_pages=4)
    eng = ContinuousBatchingEngine(params, cfg, decp, ecfg)
    reqs = [Request(rid=i, arrival=0.0, max_new=6,
                    prompt=np.full((4,), 7 + i, np.int32))
            for i in range(2)]
    for r in reqs:
        eng.queue_prefill(r)
    eng.run_prefills()
    assert eng.attach_ready() == 1          # second record does not fit
    before = eng.num_attach_backpressure
    assert eng.attach_ready() == 0          # head-of-line wait, no skip
    assert eng.num_attach_backpressure > before
    done = []
    while eng.handoff_backlog() or eng.has_active():
        eng.attach_ready()
        if eng.has_active():
            done += eng.step()
    assert sorted(f.rid for f in done) == [0, 1]
    # unified reference under the SAME tiny pool (the scheduler requeues
    # its page-pool bounces): streams must still match bitwise
    uref = ContinuousBatchingEngine(
        params, cfg, decp,
        dataclasses.replace(ecfg, prefill_slots=0, handoff_cap=0))
    sched = Scheduler(uref)
    for r in reqs:
        sched.submit(dataclasses.replace(r))
    _check_identical(done, {f.rid: f for f in sched.run()})


# ---------------------------------------------------------------------------
# Async-stream observables: timers, overlap, sync accounting
# ---------------------------------------------------------------------------


def test_phase_timers_and_overlap(stack):
    """The engine's spans attribute host time per phase (admission, step
    dispatch, status pull and retirement), and with two active groups each
    step harvests group A while group B's device step is still in flight —
    ``num_overlap_harvests`` counts exactly stepped_groups - 1 per step."""
    cfg, params, dec = stack
    ecfg = dataclasses.replace(ECFG, handoff_cap=8)
    eng = ContinuousBatchingEngine(params, cfg, dec, ecfg,
                                   policies={"exact": 1, "topk": 1})
    sched = Scheduler(eng)
    before = tracing.totals()
    rng = np.random.default_rng(5)
    for i in range(6):
        sched.submit(Request(
            rid=i, arrival=0.0, policy=("exact", "topk")[i % 2],
            prompt=rng.integers(0, cfg.vocab_size,
                                size=int(rng.integers(2, 7))),
            max_new=int(rng.integers(3, 11))))
    finished = sched.run()
    assert len(finished) == 6
    spent = {name: t["seconds_total"]
             - before.get(name, {}).get("seconds_total", 0.0)
             for name, t in tracing.totals().items()}
    assert spent["serve.engine.prefill"] > 0.0     # disaggregated admission
    assert spent["serve.engine.attach"] > 0.0
    assert spent["serve.engine.dispatch"] > 0.0
    assert spent["serve.engine.sync"] + spent["serve.engine.harvest"] > 0.0
    # both groups were active together at least once -> overlapped harvest
    assert eng.num_overlap_harvests > 0
    # overlap never exceeds (groups - 1) per step taken
    assert eng.num_overlap_harvests <= eng.num_steps


def test_one_fused_sync_per_group_step_preserved(stack, disagg):
    """PR 5's contract survives disaggregation: worker prefill + attach
    cost ZERO device->host syncs; each group step costs exactly one fused
    status pull, plus one harvest pull per finishing group."""
    cfg, _, _ = stack
    reqs = _workload(cfg, seed=3, n=4)
    todo, done = list(reqs), []
    before = disagg.num_host_syncs
    while todo and disagg.handoff_free() > 0:
        disagg.queue_prefill(todo.pop(0))
    disagg.run_prefills()
    disagg.attach_ready()
    assert disagg.num_host_syncs == before   # admission path is sync-free
    steps = pulls = 0
    while todo or disagg.handoff_backlog() or disagg.has_active():
        while todo and disagg.handoff_free() > 0:
            disagg.queue_prefill(todo.pop(0))
        disagg.run_prefills()
        disagg.attach_ready()
        if disagg.has_active():
            got = disagg.step()
            steps += 1                       # single group -> 1 status pull
            pulls += 1 if got else 0         # + 1 harvest pull if finished
            done += got
    assert disagg.num_host_syncs - before == steps + pulls
    assert len(done) == len(reqs)


def test_windowed_decode_token_identical(stack, unified):
    """``steps_per_sync > 1`` fuses up to K decode iterations into one
    dispatch (a bounded while_loop over the same traced step body, early-
    exiting when any row finishes).  Streams must stay bitwise identical
    to per-step syncing — for the unified AND the disaggregated engine —
    including per-request ``invocations`` (the early exit surfaces
    finished rows at the same iteration per-step syncing would)."""
    cfg, params, dec = stack
    reqs = _workload(cfg, seed=11, n=8)
    uref = _drive_unified(unified, [dataclasses.replace(r) for r in reqs])
    for ecfg in (dataclasses.replace(ECFG, prefill_slots=0, handoff_cap=0,
                                     steps_per_sync=3),
                 dataclasses.replace(ECFG, steps_per_sync=3)):
        eng = ContinuousBatchingEngine(params, cfg, dec, ecfg)
        sched = Scheduler(eng)
        for r in reqs:
            sched.submit(dataclasses.replace(r))
        done = sched.run()
        _check_identical(done, uref)
        # fewer syncs than steps-without-windowing: the window actually
        # fuses (every run here has stretches with no finishing row)
        assert all(v == 1 for v in eng.compile_counts().values())


def test_queue_prefill_requires_disagg_mode(stack, unified):
    cfg, _, _ = stack
    with pytest.raises(RuntimeError, match="disaggregated"):
        unified.queue_prefill(Request(rid=0, max_new=4,
                                      prompt=np.ones(3, np.int32)))
