"""Continuous-batching serving engine: token-level equivalence with the
run-to-completion decoder, slot reuse, mid-flight admission in any order,
page-pool back-pressure at admission, host-sync accounting, and the
compile-once guarantee."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st
from conftest import tiny_dense, tiny_rwkv
from repro.config import DecodeConfig
from repro.core import decode as D
from repro.models import model as M
from repro.serving import (
    ContinuousBatchingEngine,
    DecodeSession,
    EngineConfig,
    PagePoolExhausted,
    Request,
    Scheduler,
    aggregate_stats,
    tracing,
)

pytestmark = pytest.mark.serving


@pytest.fixture(scope="module")
def served():
    """One trafficked engine shared by the assertions below: 7 mixed-length
    requests through 3 slots (forcing eviction + re-admission)."""
    cfg = tiny_dense()
    params = M.init(jax.random.PRNGKey(0), cfg)
    dec = DecodeConfig(max_new_tokens=24, block_k=4, eos_id=3)
    eng = ContinuousBatchingEngine(
        params, cfg, dec, EngineConfig(num_slots=3, max_prompt_len=10,
                                       max_new_cap=24))
    sched = Scheduler(eng)
    rng = np.random.default_rng(0)
    reqs = {}
    for i in range(7):
        p = rng.integers(0, cfg.vocab_size, size=int(rng.integers(3, 11)))
        reqs[i] = Request(rid=i, prompt=p,
                          max_new=int(rng.integers(4, 25)))
        sched.submit(reqs[i])
    finished = sched.run()
    return params, cfg, dec, eng, reqs, finished


def _reference(params, cfg, dec, prompt, max_new):
    d1 = dec.replace(max_new_tokens=max_new)
    bt, bs = D.bpd_decode(params, cfg, d1, {"tokens": jnp.asarray(prompt)[None]})
    n = int(bs["text_len"][0])
    return np.asarray(bt[0, len(prompt):n])


def test_engine_matches_bpd_decode_per_request(served):
    """Every request's engine output equals its own run-to-completion
    bpd_decode — continuous batching is a scheduling change, not a
    decoding change."""
    params, cfg, dec, _, reqs, finished = served
    assert len(finished) == 7
    for f in finished:
        ref = _reference(params, cfg, dec, reqs[f.rid].prompt,
                         min(reqs[f.rid].max_new, 24))
        np.testing.assert_array_equal(f.tokens, ref)
        assert f.generated == len(ref)


def test_compile_once_under_traffic(served):
    """Admission/step/evict never recompile: static shapes by design."""
    *_, eng, _, _ = served
    assert all(v == 1 for v in eng.compile_counts().values()), \
        eng.compile_counts()


def test_slots_fully_recycled(served):
    """After draining, every slot is free and holds no *visible* KV entry.

    Eviction sets pos = -1; later steps may speculatively write the frozen
    block positions [text_len, text_len + k) into inactive rows — those are
    masked out by the visibility rule (pos >= length + k is stale once
    length rolls back to 0 on admission, which rewrites the row wholesale),
    so the invariant is: every entry is -1 or inside that frozen block.
    """
    *_, eng, _, _ = served
    assert eng.free_slots() == [0, 1, 2]
    text_len = eng.state.text_len[:, None]
    for layer in eng.state.caches:
        pos = layer["attn"]["pos"]
        ok = (pos == -1) | ((pos >= text_len) &
                            (pos < text_len + eng.block_k))
        assert bool(jnp.all(ok))


def test_per_request_stats(served):
    *_, finished = served
    stats = aggregate_stats(finished, wall_seconds=1.0)
    assert stats["requests"] == 7
    assert stats["total_tokens"] == sum(f.generated for f in finished)
    assert stats["mean_accepted"] >= 1.0
    assert stats["latency_p95_s"] >= stats["latency_p50_s"] >= 0.0
    for f in finished:
        assert f.invocations >= 2          # prefill + ≥1 iteration
        assert 0 < f.generated <= 24


def test_midflight_admission_is_equivalent():
    """A request admitted while another slot is mid-decode produces the
    same tokens as decoding it alone — slots are fully isolated."""
    cfg = tiny_dense()
    params = M.init(jax.random.PRNGKey(1), cfg)
    dec = DecodeConfig(max_new_tokens=16, block_k=4)
    eng = ContinuousBatchingEngine(
        params, cfg, dec, EngineConfig(num_slots=2, max_prompt_len=8,
                                       max_new_cap=16))
    rng = np.random.default_rng(3)
    p0 = rng.integers(0, cfg.vocab_size, size=8)
    p1 = rng.integers(0, cfg.vocab_size, size=5)
    eng.admit(Request(rid=0, prompt=p0, max_new=16))
    done = []
    for _ in range(3):                      # progress request 0 first
        done += eng.step()
    eng.admit(Request(rid=1, prompt=p1, max_new=10))
    while eng.has_active():
        done += eng.step()
    by_rid = {f.rid: f for f in done}
    np.testing.assert_array_equal(by_rid[0].tokens,
                                  _reference(params, cfg, dec, p0, 16))
    np.testing.assert_array_equal(by_rid[1].tokens,
                                  _reference(params, cfg, dec, p1, 10))


def test_sjf_policy_prefers_short_jobs():
    cfg = tiny_dense()
    params = M.init(jax.random.PRNGKey(2), cfg)
    dec = DecodeConfig(max_new_tokens=16, block_k=4)
    eng = ContinuousBatchingEngine(
        params, cfg, dec, EngineConfig(num_slots=1, max_prompt_len=6,
                                       max_new_cap=16))
    sched = Scheduler(eng, policy="sjf")
    rng = np.random.default_rng(5)
    for rid, mn in [(0, 16), (1, 2), (2, 8)]:
        sched.submit(Request(rid=rid, max_new=mn,
                             prompt=rng.integers(0, cfg.vocab_size, size=4)))
    finished = sched.run()
    # single slot: admission order == finish order == ascending max_new
    assert [f.rid for f in finished] == [1, 2, 0]


def test_admission_guards():
    cfg = tiny_dense()
    params = M.init(jax.random.PRNGKey(0), cfg)
    dec = DecodeConfig(max_new_tokens=8, block_k=4)
    eng = ContinuousBatchingEngine(
        params, cfg, dec, EngineConfig(num_slots=1, max_prompt_len=4,
                                       max_new_cap=8))
    with pytest.raises(ValueError):
        eng.admit(Request(rid=0, prompt=np.zeros(9, np.int32), max_new=4))
    # the scheduler rejects at submit time, before the serving loop,
    # so one bad request can never abort a mid-flight drain
    sched = Scheduler(eng)
    with pytest.raises(ValueError):
        sched.submit(Request(rid=3, prompt=np.zeros(9, np.int32), max_new=4))
    assert not sched.queue
    eng.admit(Request(rid=1, prompt=np.zeros(3, np.int32), max_new=4))
    with pytest.raises(RuntimeError):
        eng.admit(Request(rid=2, prompt=np.zeros(3, np.int32), max_new=4))


def test_recurrent_families_are_gated():
    """Padded-prompt prefill is unsound for recurrent state — the engine
    must refuse rather than silently serve wrong tokens."""
    cfg = tiny_rwkv()
    params = jax.eval_shape(lambda: M.init(jax.random.PRNGKey(0), cfg))
    with pytest.raises(NotImplementedError):
        ContinuousBatchingEngine(params, cfg, DecodeConfig(), EngineConfig())


@pytest.mark.parametrize("groups", [
    {"exact": 1, "adaptive": 2},                      # 2-policy mix
    {"exact": 1, "topk": 1, "adaptive": 2},           # 3-policy mix
])
def test_host_syncs_count_group_steps_not_members(groups):
    """``num_host_syncs`` accounting under policy slot grouping: one fused
    sync per GROUP STEP — never one per slot group member, and idle groups
    cost nothing.  The bound for N engine steps with g active groups is
    exactly N * g (+ one harvest pull per finishing group at the end)."""
    cfg = tiny_dense()
    params = M.init(jax.random.PRNGKey(8), cfg)
    dec = DecodeConfig(max_new_tokens=24, block_k=4)
    eng = ContinuousBatchingEngine(
        params, cfg, dec,
        EngineConfig(num_slots=sum(groups.values()), max_prompt_len=6,
                     max_new_cap=24), policies=groups)
    rng = np.random.default_rng(13)
    mk = lambda rid, pol: Request(  # noqa: E731
        rid=rid, policy=pol, max_new=24,
        prompt=rng.integers(0, cfg.vocab_size, size=6))

    # phase 1: only the multi-slot 'adaptive' group is active — with BOTH
    # of its slots occupied, so per-member accounting would double-count
    eng.admit(mk(0, "adaptive"))
    eng.admit(mk(1, "adaptive"))
    before = eng.num_host_syncs
    for _ in range(2):
        assert not eng.step()
    assert eng.num_host_syncs - before == 2      # 2 steps x 1 active group

    # phase 2: one request per remaining group — every group active
    for i, name in enumerate(n for n in groups if n != "adaptive"):
        eng.admit(mk(2 + i, name))
    before = eng.num_host_syncs
    for _ in range(2):
        assert not eng.step()
    assert eng.num_host_syncs - before == 2 * len(groups)

    # host-cache reads never sync
    eng.free_slots(), eng.has_active()
    assert eng.num_host_syncs - before == 2 * len(groups)

    # drain: every step syncs once per active group; a harvesting step
    # adds exactly one pull per group with >= 1 finishing request (two
    # requests finishing together in one group still cost ONE pull)
    before, steps, pulls = eng.num_host_syncs, 0, 0
    finished = []
    while eng.has_active():
        active = sum(1 for g in eng.groups if np.any(g.status & 1))
        done = eng.step()
        steps += active
        pulls += len({f.policy for f in done})
        finished += done
    assert len(finished) == 2 + (len(groups) - 1)
    assert eng.num_host_syncs - before == steps + pulls


def test_bpd_iteration_active_mask_freezes_rows():
    """Direct unit check of the decode.py refactor: an inactive row accepts
    nothing and keeps its state bit-for-bit."""
    cfg = tiny_dense()
    params = M.init(jax.random.PRNGKey(4), cfg)
    dec = DecodeConfig(max_new_tokens=12, block_k=4)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(5), (3, 6), 0,
                                          cfg.vocab_size)}
    state, prefix = D.bpd_prefill_causal_lm(params, cfg, dec, batch,
                                            max_new=12)
    be = D.causal_lm_backend(cfg)
    active = jnp.asarray([True, False, True])
    out = D.bpd_iteration(params, cfg, dec, be, state, prefix_offset=prefix,
                          max_new=jnp.full((3,), 12, jnp.int32),
                          active=active)
    assert int(out.generated[1]) == 0
    assert int(out.text_len[1]) == int(state.text_len[1])
    np.testing.assert_array_equal(np.asarray(out.tokens[1]),
                                  np.asarray(state.tokens[1]))
    np.testing.assert_array_equal(np.asarray(out.proposals[1]),
                                  np.asarray(state.proposals[1]))
    assert int(out.generated[0]) >= 1 and int(out.generated[2]) >= 1


def test_bpd_decode_per_row_budgets():
    """bpd_decode honors per-row max_new_rows (static-batch baseline)."""
    cfg = tiny_dense()
    params = M.init(jax.random.PRNGKey(6), cfg)
    dec = DecodeConfig(max_new_tokens=16, block_k=4)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(7), (3, 5), 0,
                                          cfg.vocab_size)}
    budgets = jnp.asarray([3, 16, 9], jnp.int32)
    _, stats = D.bpd_decode(params, cfg, dec, batch, max_new_rows=budgets)
    np.testing.assert_array_equal(np.asarray(stats["generated"]),
                                  np.asarray(budgets))


@pytest.mark.parametrize("donate", [True, False])
def test_single_device_serving_fns_donate_slot_state(donate):
    """Without a mesh the serving functions still donate the slot state
    when the session donates (the default on accelerators), so a step or
    an admission never holds two copies of the KV slab on the chip."""
    cfg = tiny_dense()
    params = M.init(jax.random.PRNGKey(0), cfg)
    sess = DecodeSession(params, cfg, DecodeConfig(max_new_tokens=8,
                                                   block_k=4), donate=donate)
    fns = sess.serving_fns(EngineConfig(num_slots=2, max_prompt_len=8,
                                        max_new_cap=8))
    state = fns.init(jnp.zeros((), jnp.int32))
    n_state = len(jax.tree_util.tree_leaves(state))
    step = fns.step.lower(params, {}, state).as_text()
    evict = fns.evict.lower(state, jnp.zeros((2,), bool)).as_text()
    assert step.count("tf.aliasing_output") == (n_state if donate else 0)
    assert evict.count("tf.aliasing_output") == (n_state if donate else 0)


# ---------------------------------------------------------------------------
# Admission in any order, page-pool back-pressure, host-sync accounting
# ---------------------------------------------------------------------------

ECFG = EngineConfig(num_slots=2, max_prompt_len=6, max_new_cap=10)


@pytest.fixture(scope="module")
def small():
    cfg = tiny_dense()
    params = M.init(jax.random.PRNGKey(0), cfg)
    dec = DecodeConfig(max_new_tokens=10, block_k=4)
    return cfg, params, dec


@pytest.fixture(scope="module")
def small_engine(small):
    cfg, params, dec = small
    return ContinuousBatchingEngine(params, cfg, dec, ECFG)


def _workload(cfg, seed, n=6):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, arrival=0.0,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=int(rng.integers(2, 7))),
                    max_new=int(rng.integers(3, 11)))
            for i in range(n)]


def _drive(eng, reqs):
    """Admit in list order whenever a slot is free, step until drained."""
    todo, done = list(reqs), []
    while todo or eng.has_active():
        while todo and eng.free_slots():
            eng.admit(todo.pop(0))
        done += eng.step()
    return {f.rid: f for f in done}


_IN_ORDER = {}   # workload seed -> streams of the in-order run


def _in_order(eng, cfg, seed):
    if seed not in _IN_ORDER:
        _IN_ORDER[seed] = _drive(eng, _workload(cfg, seed))
    return _IN_ORDER[seed]


def _check_identical(done, ref):
    assert sorted(f.rid for f in done) == sorted(ref)
    for f in done:
        r = ref[f.rid]
        np.testing.assert_array_equal(
            f.tokens, r.tokens,
            err_msg=f"rid={f.rid}: stream diverged from the reference run")
        assert f.generated == r.generated, f.rid
        assert f.invocations == r.invocations, f.rid


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 2**16), order=st.permutations(range(6)),
       ops=st.lists(st.sampled_from("aas"), min_size=4, max_size=40))
def test_any_interleaving_token_identical(small, small_engine, seed, order,
                                          ops):
    """Hypothesis drives the engine through an ARBITRARY sequence of
    (a)dmit and decode (s)tep, admitting the requests in a random order,
    then drains.  Whenever each request enters its slot, and next to
    whichever others, its stream matches the in-order run bitwise; an
    admit with no free slot raises and changes nothing."""
    cfg, _, _ = small
    eng = small_engine
    ref = _in_order(eng, cfg, seed)
    reqs = _workload(cfg, seed)
    todo, done = [reqs[i] for i in order], []
    for op in ops:
        if op == "a" and todo:
            if not eng.free_slots():
                with pytest.raises(RuntimeError, match="no free slot"):
                    eng.admit(todo[0])
            else:
                eng.admit(todo.pop(0))
        elif op == "s" and eng.has_active():
            done += eng.step()
    done += _drive(eng, todo).values()
    _check_identical(done, ref)
    # the module-scoped engine is reused across examples: geometry never
    # changes, so nothing may ever recompile
    assert all(v == 1 for v in eng.compile_counts().values()), \
        eng.compile_counts()


def test_admit_backpressure_page_pool(small):
    """A paged pool that fits one request: admitting the second raises
    ``PagePoolExhausted`` before any device work, leaving its slot free
    and the allocator consistent; once the first retires and releases its
    pages the second admits, and both streams equal a roomy pool's."""
    cfg, params, dec = small
    decp = dec.replace(cache_backend="paged", page_size=8)
    # each request spans 2 pages (prompt 4 + budget 6 + lookahead 4 over
    # size-8 pages); pool = 1 trash + 3 allocatable, so ONE admitted
    # request fits but two cannot coexist
    eng = ContinuousBatchingEngine(
        params, cfg, decp, dataclasses.replace(ECFG, page_pool_pages=4))
    reqs = [Request(rid=i, arrival=0.0, max_new=6,
                    prompt=np.full((4,), 7 + i, np.int32))
            for i in range(2)]
    pages = eng.groups[0].pages
    eng.admit(reqs[0])
    with pytest.raises(PagePoolExhausted):
        eng.admit(reqs[1])
    assert len(eng.free_slots()) == 1
    pages.check_invariants()
    done = []
    while eng.has_active():
        done += eng.step()
    assert [f.rid for f in done] == [0]
    eng.admit(reqs[1])
    while eng.has_active():
        done += eng.step()
    pages.check_invariants()
    roomy = ContinuousBatchingEngine(params, cfg, decp, ECFG)
    _check_identical(done, _drive(roomy, [dataclasses.replace(r)
                                          for r in reqs]))


def test_phase_timers_and_overlap(small):
    """The engine's spans attribute host time per phase (admission, step
    dispatch, status pull and retirement), and with two active groups each
    step harvests group A while group B's device step is still in flight —
    ``num_overlap_harvests`` counts exactly stepped_groups - 1 per step."""
    cfg, params, dec = small
    eng = ContinuousBatchingEngine(params, cfg, dec, ECFG,
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
    assert spent["serve.engine.admit"] > 0.0
    assert spent["serve.engine.dispatch"] > 0.0
    assert spent["serve.engine.sync"] + spent["serve.engine.harvest"] > 0.0
    # both groups were active together at least once -> overlapped harvest
    assert eng.num_overlap_harvests > 0
    # overlap never exceeds (groups - 1) per step taken
    assert eng.num_overlap_harvests <= eng.num_steps


def test_one_fused_sync_per_group_step_preserved(small, small_engine):
    """Admission costs ZERO device->host syncs; each group step costs
    exactly one fused status pull, plus one harvest pull per finishing
    group."""
    cfg, _, _ = small
    eng = small_engine
    todo, done = _workload(cfg, seed=3, n=4), []
    before = eng.num_host_syncs
    steps = pulls = 0
    while todo or eng.has_active():
        while todo and eng.free_slots():
            n = eng.num_host_syncs
            eng.admit(todo.pop(0))
            assert eng.num_host_syncs == n   # admission is sync-free
        got = eng.step()
        steps += 1                           # single group -> 1 status pull
        pulls += 1 if got else 0             # + 1 harvest pull if finished
        done += got
    assert eng.num_host_syncs - before == steps + pulls
    assert len(done) == 4


def test_windowed_decode_token_identical(small, small_engine):
    """``steps_per_sync > 1`` fuses up to K decode iterations into one
    dispatch (a bounded while_loop over the same traced step body, early-
    exiting when any row finishes).  Streams must stay bitwise identical
    to per-step syncing, including per-request ``invocations`` (the early
    exit surfaces finished rows at the same iteration per-step syncing
    would)."""
    cfg, params, dec = small
    reqs = _workload(cfg, seed=11, n=8)
    ref = _drive(small_engine, [dataclasses.replace(r) for r in reqs])
    eng = ContinuousBatchingEngine(
        params, cfg, dec, dataclasses.replace(ECFG, steps_per_sync=3))
    sched = Scheduler(eng)
    for r in reqs:
        sched.submit(dataclasses.replace(r))
    _check_identical(sched.run(), ref)
    assert all(v == 1 for v in eng.compile_counts().values())


def test_prefill_rows_match_admit(small):
    """``prefill`` at width W builds, row for row, bitwise the state that
    ``admit`` installs at width 1: caches, first-block proposals, policy
    state, token buffer and prompt length.  This batch-size invariance is
    what ``chip_smoke.py``'s cached verify relies on when it prefills a
    whole batch of prompts at once."""
    cfg, params, dec = small
    w, plen = 3, ECFG.max_prompt_len
    fns = DecodeSession(params, cfg, dec).serving_fns(
        dataclasses.replace(ECFG, num_slots=w))
    rng = np.random.default_rng(17)
    prompts = np.zeros((w, plen), np.int32)
    plens = rng.integers(2, plen + 1, size=w).astype(np.int32)
    for i, p in enumerate(plens):
        prompts[i, :p] = rng.integers(0, cfg.vocab_size, size=p)
    packet = fns.prefill(params, {}, jnp.asarray(prompts),
                         jnp.asarray(plens), jnp.asarray(prompts))
    state = fns.init(jnp.zeros((), jnp.int32))
    for i in range(w):
        state = fns.admit(params, {}, state, jnp.asarray(i, jnp.int32),
                          jnp.asarray(prompts[i]),
                          jnp.asarray(plens[i], jnp.int32),
                          jnp.asarray(ECFG.max_new_cap, jnp.int32),
                          jnp.asarray(prompts[i]))
    np.testing.assert_array_equal(np.asarray(state.prompt_len),
                                  np.asarray(packet.prompt_len))
    for field in ("tokens", "proposals", "caches", "policy_state"):
        slot_leaves = jax.tree_util.tree_leaves(getattr(state, field))
        row_leaves = jax.tree_util.tree_leaves(getattr(packet, field))
        assert len(slot_leaves) == len(row_leaves), field
        for a, b in zip(slot_leaves, row_leaves):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=field)
