"""Continuous-batching serving engine: token-level equivalence with the
run-to-completion decoder, slot reuse, mid-flight admission, and the
compile-once guarantee."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_dense, tiny_rwkv
from repro.config import DecodeConfig
from repro.core import decode as D
from repro.models import model as M
from repro.serving import (
    ContinuousBatchingEngine,
    EngineConfig,
    Request,
    Scheduler,
    aggregate_stats,
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
    from repro.serving import DecodeSession

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
