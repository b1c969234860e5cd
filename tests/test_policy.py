"""DecodePolicy API: acceptance semantics, schedule properties, drafter
losslessness, legacy criterion-string equivalence, and the serving engine's
per-slot policy-state lifecycle + single-sync step loop."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st
from conftest import tiny_dense, tiny_seq2seq
from repro.config import DecodeConfig, get_policy, list_policies
from repro.core import decode as D
from repro.core import policy as P
from repro.models import model as M
from repro.models import seq2seq as S
from repro.serving import ContinuousBatchingEngine, EngineConfig, Request

I32 = jnp.int32


# ---------------------------------------------------------------------------
# Acceptor semantics (property tests)
# ---------------------------------------------------------------------------


def _random_case(seed, b=4, k=5, vocab=13):
    rng = np.random.default_rng(seed)
    props = jnp.asarray(rng.integers(0, vocab, (b, k)), I32)
    logits = jnp.asarray(rng.normal(size=(b, k, vocab)), jnp.float32)
    return props, logits


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), top_k=st.integers(1, 5))
def test_exact_accepts_subset_of_topk(seed, top_k):
    """Every exact-accepted position is top-k-accepted (any k >= 1), so
    exact-accepted prefixes are a subset of top-k-accepted prefixes."""
    props, logits = _random_case(seed)
    exact = P.ExactAcceptor().accepts(props, logits)
    topk = P.TopKAcceptor(top_k=top_k).accepts(props, logits)
    assert bool(jnp.all(~exact | topk))
    # prefix lengths inherit the ordering
    khat_e, _ = P.StaticSchedule().block_size(exact, jnp.full((4,), 99), ())
    khat_t, _ = P.StaticSchedule().block_size(topk, jnp.full((4,), 99), ())
    assert bool(jnp.all(khat_e <= khat_t))


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), m1=st.integers(1, 6), m2=st.integers(1, 6),
       remaining=st.integers(1, 8))
def test_khat_monotone_in_min_block_and_clamped(seed, m1, m2, remaining):
    """k̂ is monotone in min_block, always in [1, k], and clamped by the
    remaining budget."""
    rng = np.random.default_rng(seed)
    k = 5
    accepts = jnp.asarray(rng.random((3, k)) < 0.5).at[:, 0].set(True)
    rem = jnp.full((3,), remaining, I32)
    lo, hi = min(m1, m2), max(m1, m2)
    khat_lo, _ = P.StaticSchedule(min_block=lo).block_size(accepts, rem, ())
    khat_hi, _ = P.StaticSchedule(min_block=hi).block_size(accepts, rem, ())
    assert bool(jnp.all(khat_lo <= khat_hi))
    for khat in (khat_lo, khat_hi):
        assert bool(jnp.all(khat >= 1))
        assert bool(jnp.all(khat <= max(remaining, 1)))
        assert bool(jnp.all(khat <= k))


def test_exact_acceptor_matches_legacy_semantics():
    """Acceptor objects reproduce the seed position_accepts semantics."""
    props = jnp.asarray([[7, 4, 5, 6]])
    logits = np.zeros((1, 4, 11), np.float32)
    for j, g in enumerate([4, 5, 9, 0]):
        logits[0, j, g] = 5.0
    acc = P.ExactAcceptor().accepts(props, jnp.asarray(logits))
    np.testing.assert_array_equal(np.asarray(acc),
                                  [[True, True, True, False]])


# ---------------------------------------------------------------------------
# Adaptive schedule
# ---------------------------------------------------------------------------


def test_adaptive_schedule_cap_tracks_acceptance():
    sched = P.AdaptiveSchedule(decay=0.5, grow=0.8, shrink=0.4)
    b, k = 2, 6
    state = sched.init_state(b)
    rem = jnp.full((b,), 99, I32)
    none = jnp.zeros((b, k), bool).at[:, 0].set(True)   # accept nothing extra
    allacc = jnp.ones((b, k), bool)
    # sustained rejection shrinks the cap (it keeps probing upward from 1,
    # so the equilibrium is small but not pinned at exactly 1)
    for _ in range(12):
        khat, state = sched.block_size(none, rem, state)
        assert bool(jnp.all(khat >= 1)) and bool(jnp.all(khat <= k))
    assert int(jnp.max(state["cap"])) <= 2
    # sustained acceptance grows it back to the full block
    for _ in range(30):
        khat, state = sched.block_size(allacc, rem, state)
    assert int(jnp.min(state["cap"])) == k
    khat, _ = sched.block_size(allacc, rem, state)
    assert bool(jnp.all(khat == k))


def test_adaptive_cap_shrinks_then_recovers_stepwise():
    """Deterministic cap dynamics, asserted step-by-step: sustained
    rejection walks the k̂-driven cap down toward min_block, sustained
    acceptance walks it back up to the full block — each step checked
    against an independent float32 replica of the documented controller
    (EMA of accepted/cap; cap +1 above ``grow``, -1 below ``shrink``)."""
    k, rem = 4, jnp.full((1,), 99, I32)
    sched = P.AdaptiveSchedule(min_block=1, decay=0.5, grow=0.8, shrink=0.45)
    state = sched.init_state(1)
    reject = jnp.zeros((1, k), bool).at[:, 0].set(True)   # prefix = 1
    accept = jnp.ones((1, k), bool)                       # prefix = k
    phases = [(reject, 8), (accept, 10)]

    rate, cap = np.float32(1.0), k          # replica state (cap pre-clip)
    caps, khats = [], []
    for accepts, steps in phases:
        prefix = 1 if accepts is reject else k
        for _ in range(steps):
            khat, state = sched.block_size(accepts, rem, state)
            cap = min(max(cap, 1), k)                      # clip into [1, k]
            accepted = min(max(prefix, 1), cap)
            want_khat = min(accepted, 99)
            rate = np.float32(rate * np.float32(0.5)
                              + np.float32(0.5) * np.float32(accepted)
                              / np.float32(cap))
            if rate >= np.float32(0.8):
                cap = min(cap + 1, k)
            elif rate <= np.float32(0.45):
                cap = max(cap - 1, 1)
            assert int(khat[0]) == want_khat, (len(khats), khat, want_khat)
            assert int(state["cap"][0]) == cap, (len(caps), state, cap)
            assert np.float32(state["rate"][0]) == pytest.approx(rate,
                                                                 abs=1e-6)
            caps.append(int(state["cap"][0]))
            khats.append(int(khat[0]))

    # milestones: the rejection phase shrank the cap to (near) min_block,
    # and the acceptance phase recovered it to the full block
    assert min(caps[:8]) <= 2, caps
    assert caps[8:].count(k) >= 1 and caps[-1] == k, caps
    assert khats[-1] == k                   # recovered cap re-enables k̂ = k
    # during sustained full acceptance, k̂ is pinned to the (growing) cap:
    # it climbs monotonically back to k instead of jumping there
    recovery = khats[8:]
    assert recovery == sorted(recovery), recovery
    assert recovery[0] < k, recovery        # the shrunk cap really bound k̂


def test_adaptive_rows_are_independent():
    sched = P.AdaptiveSchedule(decay=0.5)
    state = sched.init_state(2)
    rem = jnp.full((2,), 99, I32)
    acc = jnp.stack([jnp.ones((4,), bool),
                     jnp.zeros((4,), bool).at[0].set(True)])
    for _ in range(10):
        _, state = sched.block_size(acc, rem, state)
    assert int(state["cap"][0]) > int(state["cap"][1])


# ---------------------------------------------------------------------------
# Legacy criterion strings == policy objects (token-identical)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def dense_model():
    cfg = tiny_dense()
    params = M.init(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (3, 6), 0,
                                          cfg.vocab_size)}
    return cfg, params, batch


ACCEPTORS = {"exact": P.ExactAcceptor(),
             "topk": P.TopKAcceptor(top_k=2),
             "distance": P.DistanceAcceptor(epsilon=2.0)}


@pytest.mark.parametrize("criterion", sorted(ACCEPTORS))
def test_criterion_strings_alias_policy_objects(criterion, dense_model):
    """dec.criterion strings, dec.policy names, and hand-built DecodePolicy
    objects all decode token-identically."""
    cfg, params, batch = dense_model
    dec = DecodeConfig(max_new_tokens=12, block_k=4, criterion=criterion,
                       top_k=2, epsilon=2.0)
    ref_t, ref_s = D.bpd_decode(params, cfg, dec, batch)

    by_name_t, by_name_s = D.bpd_decode(
        params, cfg, dec.replace(criterion="exact", policy=criterion), batch)
    obj = P.DecodePolicy(P.HeadsDrafter(), ACCEPTORS[criterion],
                         P.StaticSchedule(), name="hand-built")
    by_obj_t, by_obj_s = D.bpd_decode(params, cfg, dec, batch, policy=obj)

    for t, s in ((by_name_t, by_name_s), (by_obj_t, by_obj_s)):
        np.testing.assert_array_equal(np.asarray(ref_t), np.asarray(t))
        np.testing.assert_array_equal(np.asarray(ref_s["generated"]),
                                      np.asarray(s["generated"]))
        assert int(ref_s["iterations"]) == int(s["iterations"])


def test_resolve_policy_precedence_and_errors():
    dec = DecodeConfig(criterion="topk", policy="exact", top_k=3)
    assert P.resolve_policy(dec).name == "exact"          # policy > criterion
    assert P.resolve_policy(dec, "distance").name == "distance"  # arg wins
    obj = P.DecodePolicy(P.HeadsDrafter(), P.ExactAcceptor(),
                         P.StaticSchedule())
    assert P.resolve_policy(dec, obj) is obj
    with pytest.raises(ValueError, match="unknown decode policy"):
        P.resolve_policy(dec.replace(policy="nope"))
    # config-level resolution used by launchers
    assert get_policy(dec).name == "exact"
    assert {"exact", "topk", "distance", "adaptive", "input_copy",
            "topk_tree"} <= set(list_policies())


# ---------------------------------------------------------------------------
# Drafters: losslessness + draft mechanics
# ---------------------------------------------------------------------------


def test_topk_tree_drafter_is_lossless_causal(dense_model):
    """Changing the drafter never changes tokens under exact acceptance —
    slot 0 stays the verified greedy token, so only iteration counts move."""
    cfg, params, batch = dense_model
    dec = DecodeConfig(max_new_tokens=12, block_k=4)
    ref_t, ref_s = D.bpd_decode(params, cfg, dec, batch)
    t, s = D.bpd_decode(params, cfg, dec, batch, policy="topk_tree")
    np.testing.assert_array_equal(np.asarray(ref_t), np.asarray(t))
    np.testing.assert_array_equal(np.asarray(ref_s["text_len"]),
                                  np.asarray(s["text_len"]))


@pytest.mark.parametrize("policy", ["input_copy", "topk_tree", "adaptive"])
def test_new_policies_are_lossless_seq2seq(policy):
    cfg = tiny_seq2seq()
    params = S.init(jax.random.PRNGKey(2), cfg)
    dec = DecodeConfig(max_new_tokens=10, block_k=4)
    batch = {"src": jax.random.randint(jax.random.PRNGKey(3), (2, 6), 1,
                                       cfg.vocab_size)}
    ref, ref_s = D.bpd_decode_seq2seq(params, cfg, dec, batch)
    out, s = D.bpd_decode_seq2seq(params, cfg, dec, batch, policy=policy)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    np.testing.assert_array_equal(np.asarray(ref_s["generated"]),
                                  np.asarray(s["generated"]))


def test_input_copy_drafts_source_aligned():
    """Unit check of the draft mechanics: slots >= 1 copy the source at the
    output positions the block covers; slot 0 is the verified greedy."""
    drafter = P.InputCopyDrafter()
    src = jnp.asarray([[10, 11, 12, 13, 14, 15]], I32)
    state = drafter.init_state(None, None, {"src": src}, 1)
    b, K, V = 1, 4, 20
    logits = np.full((b, K, V), -10.0, np.float32)
    logits[0, 0, 7] = 10.0          # p_1 argmax at accepted slot 1 -> 7
    inputs = P.DraftInputs(
        logits=jnp.asarray(logits), khat=jnp.asarray([2], I32),
        slot=jnp.asarray([1], I32), text_len=jnp.asarray([3], I32),
        old_proposals=jnp.zeros((1, 4), I32))
    props, _ = drafter.draft(inputs, state)
    # text_len=3 -> block covers output indices 2..5 -> src[2..5]; slot 0
    # replaced by the verified token 7
    np.testing.assert_array_equal(np.asarray(props), [[7, 13, 14, 15]])


def test_input_copy_rejects_promptless_paths():
    with pytest.raises(ValueError, match="seq2seq"):
        P.InputCopyDrafter().init_state(None, None, None, 2)
    with pytest.raises(ValueError, match="seq2seq"):
        P.InputCopyDrafter().init_state(None, None, {"tokens": None}, 2)


# ---------------------------------------------------------------------------
# Serving engine: policy threading, per-slot state lifecycle, sync count
# ---------------------------------------------------------------------------


@pytest.mark.serving
def test_engine_policy_matches_run_to_completion(dense_model):
    """The engine with a non-default policy serves the same tokens as the
    run-to-completion path under that policy."""
    cfg, params, _ = dense_model
    dec = DecodeConfig(max_new_tokens=12, block_k=4)
    eng = ContinuousBatchingEngine(
        params, cfg, dec, EngineConfig(num_slots=2, max_prompt_len=6,
                                       max_new_cap=12), policy="topk_tree")
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, size=6) for _ in range(3)]
    done = []
    for i, p in enumerate(prompts):
        while not eng.free_slots():     # third request waits for an eviction
            done += eng.step()
        eng.admit(Request(rid=i, prompt=p, max_new=12))
        if i == 1:
            done += eng.step()          # mid-flight progress between admits
    while eng.has_active():
        done += eng.step()
    for f in done:
        ref_t, ref_s = D.bpd_decode(
            params, cfg, dec, {"tokens": jnp.asarray(prompts[f.rid])[None]},
            policy="topk_tree")
        n = int(ref_s["text_len"][0])
        np.testing.assert_array_equal(f.tokens, np.asarray(ref_t[0, 6:n]))


@pytest.mark.serving
def test_engine_resets_policy_state_on_admit_and_evict(dense_model):
    """A freshly admitted request must not inherit the previous occupant's
    schedule state (and evicted slots drop theirs)."""
    cfg, params, _ = dense_model
    dec = DecodeConfig(max_new_tokens=8, block_k=4)
    eng = ContinuousBatchingEngine(
        params, cfg, dec, EngineConfig(num_slots=1, max_prompt_len=6,
                                       max_new_cap=8), policy="adaptive")
    fresh_cap = int(np.asarray(eng.state.policy_state.schedule["cap"])[0])
    rng = np.random.default_rng(9)
    eng.admit(Request(rid=0, prompt=rng.integers(0, cfg.vocab_size, size=6),
                      max_new=8))
    done = []
    while eng.has_active():
        done += eng.step()
    assert len(done) == 1
    # the untrained model accepts ~nothing, so request 0 dragged the
    # adaptive cap down; eviction must have reset it
    cap_after = int(np.asarray(eng.state.policy_state.schedule["cap"])[0])
    rate_after = float(np.asarray(eng.state.policy_state.schedule["rate"])[0])
    assert cap_after == fresh_cap
    assert rate_after == 1.0
    eng.admit(Request(rid=1, prompt=rng.integers(0, cfg.vocab_size, size=6),
                      max_new=8))
    cap_admit = int(np.asarray(eng.state.policy_state.schedule["cap"])[0])
    assert cap_admit == fresh_cap


@pytest.mark.serving
def test_engine_step_is_single_host_sync(dense_model):
    """ROADMAP scheduler item: the host loop must round-trip exactly ONE
    device array per step (the fused active/finished status), not one each
    for active and finished — and a no-finish harvest pulls nothing."""
    cfg, params, _ = dense_model
    dec = DecodeConfig(max_new_tokens=16, block_k=4)
    eng = ContinuousBatchingEngine(
        params, cfg, dec, EngineConfig(num_slots=2, max_prompt_len=6,
                                       max_new_cap=16))
    rng = np.random.default_rng(11)
    eng.admit(Request(rid=0, prompt=rng.integers(0, cfg.vocab_size, size=6),
                      max_new=16))
    before = eng.num_host_syncs
    n_steps, finished = 0, []
    for _ in range(3):                      # request needs >= 4 iterations
        finished += eng.step()
        n_steps += 1
    assert not finished
    assert eng.num_host_syncs - before == n_steps
    # free_slots / has_active read the host cache — still no extra syncs
    eng.free_slots(), eng.has_active()
    assert eng.num_host_syncs - before == n_steps
    # draining the request costs the per-step sync + one harvest pull
    while eng.has_active():
        finished += eng.step()
        n_steps += 1
    assert len(finished) == 1
    assert eng.num_host_syncs - before == n_steps + 1
