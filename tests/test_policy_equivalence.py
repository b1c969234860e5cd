"""Policy-equivalence harness for per-request decode policies.

The gate for the serving engine's policy slot grouping: a MIXED-policy
engine run — heterogeneous ``Request.policy`` fields served by per-policy
slot groups, with admission and eviction interleaved mid-flight — must be
per-request token-identical to a single-policy ``DecodeSession`` run of
the same request.  Covered mixes: {exact, topk, input_copy, topk_tree,
draft_model} × {fcfs, sjf} on a single device, and a 2×2
("data", "model") mesh variant (skips on 1-device hosts, runs in the CI
``sharded`` job).
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_dense
from repro.config import DecodeConfig, ModelConfig
from repro.core.bundle import ModelBundle
from repro.models import model as M
from repro.serving import (
    ContinuousBatchingEngine,
    DecodeSession,
    EngineConfig,
    Request,
    Scheduler,
)

pytestmark = pytest.mark.serving

# one slot group per policy in the mix; input_copy drafts from Request.src
# (defaulting to the prompt) and draft_model runs the auxiliary bundle
MIX = ("exact", "topk", "input_copy", "topk_tree", "draft_model")


@pytest.fixture(scope="module")
def stack():
    cfg = tiny_dense()
    params = M.init(jax.random.PRNGKey(0), cfg)
    # top_k=2 makes the topk group genuinely diverge from exact tokens
    dec = DecodeConfig(max_new_tokens=12, block_k=4, top_k=2)
    dcfg = ModelConfig(name="tiny-draft", num_layers=1, d_model=32,
                       num_heads=2, num_kv_heads=2, d_ff=64,
                       vocab_size=cfg.vocab_size, bpd_enabled=False,
                       max_seq_len=512, dtype="float32")
    dparams = M.init(jax.random.PRNGKey(9), dcfg)
    bundles = {"draft": ModelBundle(dparams, dcfg)}
    return cfg, params, dec, bundles


def _workload(cfg, ecfg, n_per_policy=2, seed=7, mix=MIX):
    """n_per_policy requests per policy in ``mix``, mixed prompt lengths
    and budgets — more requests than slots, so groups evict and
    re-admit."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n_per_policy):
        for p in mix:
            plen = int(rng.integers(3, ecfg.max_prompt_len + 1))
            reqs.append(Request(
                rid=len(reqs), policy=p,
                prompt=rng.integers(0, cfg.vocab_size, size=plen),
                max_new=int(rng.integers(4, ecfg.max_new_cap + 1))))
    return reqs


_REF_CACHE = {}  # (policy, prompt, src, max_new) -> result; the fcfs and
                 # sjf parametrizations verify the identical workload, so
                 # memoizing halves the suite's reference decodes


def _single_policy_reference(stack, req, ecfg):
    """The gate's reference: a SINGLE-policy DecodeSession run of exactly
    this request (its own policy, its own budget, no other traffic)."""
    cfg, params, dec, bundles = stack
    pol = req.policy or dec.criterion
    max_new = min(req.max_new, ecfg.max_new_cap)
    src_key = None if req.src is None else req.src.tobytes()
    key = (pol, req.prompt.tobytes(), src_key, max_new)
    if key in _REF_CACHE:
        return _REF_CACHE[key]
    d = dec.replace(max_new_tokens=max_new)
    sess = DecodeSession(params, cfg, d, policy=pol,
                         bundles=bundles if pol == "draft_model" else None)
    batch = {"tokens": jnp.asarray(req.prompt)[None]}
    if pol == "input_copy":
        # the engine's admission pads src to the admission geometry — feed
        # the reference the identical padded row so even draft contents
        # (and therefore iteration counts) line up
        src = np.zeros((ecfg.max_prompt_len,), np.int32)
        toks = req.prompt if req.src is None else req.src
        src[:len(toks)] = toks
        batch["src"] = jnp.asarray(src)[None]
    out, stats = sess.decode(batch)
    n = int(stats["text_len"][0])
    _REF_CACHE[key] = (np.asarray(out[0, len(req.prompt):n]),
                       int(stats["generated"][0]))
    return _REF_CACHE[key]


def _check_all(stack, ecfg, finished, reqs):
    by_rid = {f.rid: f for f in finished}
    assert sorted(by_rid) == [r.rid for r in reqs]
    for r in reqs:
        f = by_rid[r.rid]
        assert f.policy == (r.policy or "exact")
        ref_toks, ref_gen = _single_policy_reference(stack, r, ecfg)
        np.testing.assert_array_equal(
            f.tokens, ref_toks,
            err_msg=f"rid={r.rid} policy={r.policy}: mixed-policy engine "
                    f"tokens diverge from the single-policy session run")
        assert f.generated == ref_gen, (r.rid, r.policy)


@pytest.mark.parametrize("sched_policy,mix,n_per_policy", [
    pytest.param("fcfs", MIX, 2, id="fcfs"),
    pytest.param("sjf", MIX, 2, id="sjf"),
    pytest.param("fcfs", ("exact", "topk_tree", "draft_model"), 3,
                 id="chain-tree-draft"),
])
def test_mixed_policy_engine_token_identical(stack, sched_policy, mix,
                                             n_per_policy):
    """One slot per policy group, more requests per policy than its slot
    holds (the 5-policy mix with 2 each; {exact, topk_tree, draft_model}
    with 3 each): every group evicts and re-admits while other groups are
    mid-decode — admission/eviction interleave across heterogeneous
    policies, and every request still decodes exactly like a lone
    single-policy session run."""
    cfg, params, dec, bundles = stack
    ecfg = EngineConfig(num_slots=len(mix), max_prompt_len=6, max_new_cap=12)
    eng = ContinuousBatchingEngine(params, cfg, dec, ecfg, bundles=bundles,
                                   policies={p: 1 for p in mix})
    sched = Scheduler(eng, policy=sched_policy)
    reqs = _workload(cfg, ecfg, n_per_policy=n_per_policy, mix=mix)
    for r in reqs:
        sched.submit(r)
    finished = sched.run()
    _check_all(stack, ecfg, finished, reqs)
    # every distinct (policy, geometry) compiled exactly once under all
    # that admission/eviction traffic
    assert all(v == 1 for v in eng.compile_counts().values()), \
        eng.compile_counts()
    # every group's device-side state is stamped with its own group id
    # (SlotBatch.group metadata survives admit/step/evict round trips)
    for g in eng.groups:
        assert np.all(np.asarray(g.state.group) == g.gid), g.name


def test_midflight_admission_across_groups(stack):
    """Engine-level interleaving: requests admitted while OTHER policy
    groups are mid-decode (and after their own group evicted a finished
    request) keep their single-policy decode exactly."""
    cfg, params, dec, bundles = stack
    ecfg = EngineConfig(num_slots=3, max_prompt_len=6, max_new_cap=12)
    eng = ContinuousBatchingEngine(
        params, cfg, dec, ecfg, bundles=bundles,
        policies={"exact": 1, "topk_tree": 1, "draft_model": 1})
    rng = np.random.default_rng(11)
    mk = lambda rid, pol, mn: Request(  # noqa: E731
        rid=rid, policy=pol, max_new=mn,
        prompt=rng.integers(0, cfg.vocab_size, size=int(rng.integers(3, 7))))
    reqs = [mk(0, "exact", 12), mk(1, "topk_tree", 4), mk(2, "draft_model", 6),
            mk(3, "topk_tree", 8), mk(4, "exact", 5)]
    done = []
    eng.admit(reqs[0])
    done += eng.step()                      # exact is mid-decode...
    eng.admit(reqs[1])                      # ...when topk_tree admits
    eng.admit(reqs[2])
    while not eng.free_slots("topk_tree"):  # rid 1 evicts mid-flight
        done += eng.step()
    eng.admit(reqs[3])                      # re-admission into the freed slot
    while not eng.free_slots("exact"):      # rid 0 evicts while 3 decodes
        done += eng.step()
    eng.admit(reqs[4])
    while eng.has_active():
        done += eng.step()
    _check_all(stack, ecfg, done, reqs)


def test_unconfigured_policy_is_rejected(stack):
    cfg, params, dec, bundles = stack
    ecfg = EngineConfig(num_slots=2, max_prompt_len=6, max_new_cap=12)
    eng = ContinuousBatchingEngine(params, cfg, dec, ecfg,
                                   policies={"exact": 1, "topk_tree": 1})
    req = Request(rid=0, prompt=np.ones(4, np.int32), max_new=4,
                  policy="adaptive")
    with pytest.raises(ValueError, match="no slot group"):
        eng.admit(req)
    # unknown names fail with the registry's message, not a KeyError
    with pytest.raises(ValueError, match="unknown decode policy"):
        eng.admit(dataclasses.replace(req, policy="nope"))
    # the scheduler rejects at submit time, before a drain could abort
    sched = Scheduler(eng)
    with pytest.raises(ValueError, match="no slot group"):
        sched.submit(req)
    assert not sched.queue


def test_caller_supplied_policy_object_is_served(stack):
    """A hand-built / modified DecodePolicy OBJECT passed as the session
    default must actually be served — not silently replaced by the
    registry entry of the same name (regression: the default group once
    re-resolved the policy by NAME)."""
    from repro.config import get_policy
    from repro.core.policy import TopKAcceptor

    cfg, params, dec, _ = stack
    custom = dataclasses.replace(get_policy(dec, "topk"),
                                 acceptor=TopKAcceptor(top_k=7),
                                 name="custom")
    ecfg = EngineConfig(num_slots=1, max_prompt_len=6, max_new_cap=8)
    eng = ContinuousBatchingEngine(params, cfg, dec, ecfg, policy=custom)
    assert eng.groups[0].policy.acceptor.top_k == 7
    assert eng.policy_names() == ["custom"]
    # ...and requests route to it by default and by its custom name
    rng = np.random.default_rng(17)
    prompt = rng.integers(0, cfg.vocab_size, size=5)
    eng.admit(Request(rid=0, prompt=prompt, max_new=8))
    done = []
    while eng.has_active():
        done += eng.step()
    sess = DecodeSession(params, cfg, dec.replace(max_new_tokens=8),
                         policy=custom)
    out, stats = sess.decode({"tokens": jnp.asarray(prompt)[None]})
    n = int(stats["text_len"][0])
    np.testing.assert_array_equal(done[0].tokens, np.asarray(out[0, 5:n]))


def test_group_partition_validation(stack):
    cfg, params, dec, _ = stack
    ecfg = EngineConfig(num_slots=4, max_prompt_len=6, max_new_cap=12)
    with pytest.raises(ValueError, match="partition"):
        ContinuousBatchingEngine(params, cfg, dec, ecfg,
                                 policies={"exact": 1, "topk_tree": 1})
    with pytest.raises(ValueError, match="at least one"):
        ContinuousBatchingEngine(params, cfg, dec, ecfg,
                                 policies={"exact": 4, "topk_tree": 0})


# ---------------------------------------------------------------------------
# Locality-aware image decoding (2-D progressive-lattice drafter/schedule)
# ---------------------------------------------------------------------------


def _locality_stack(stack):
    """The same tiny model on a 4×4/stride-2 grid geometry — the drafter
    interpolates committed neighbors and the schedule clamps blocks at
    refinement-class boundaries."""
    cfg, params, dec, bundles = stack
    return cfg, params, dec.replace(image_height=4, image_width=4,
                                    locality_stride=2), bundles


def test_locality_requires_grid_geometry(stack):
    from repro.config import get_policy

    cfg, params, dec, _ = stack
    with pytest.raises(ValueError, match="image_height"):
        get_policy(dec, "locality")


def test_locality_policy_lossless(stack):
    """Under exact acceptance the locality drafter moves iteration counts,
    never tokens: its stream equals the heads-drafted exact stream."""
    cfg, params, decl, _ = _locality_stack(stack)
    d = decl.replace(max_new_tokens=12)
    rng = np.random.default_rng(67)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(2, 4)))
    outs = {}
    for pol in ("exact", "locality"):
        out, stats = DecodeSession(params, cfg, d, policy=pol).decode(
            {"tokens": prompts})
        outs[pol] = np.asarray(out)
    np.testing.assert_array_equal(outs["locality"], outs["exact"])


def test_locality_engine_token_identical(stack):
    """The ``locality`` group in a mixed engine — admissions and evictions
    interleaved with an exact group — matches the single-policy
    DecodeSession reference per request, tokens AND generated counts."""
    cfg, params, decl, bundles = _locality_stack(stack)
    ecfg = EngineConfig(num_slots=2, max_prompt_len=6, max_new_cap=12)
    eng = ContinuousBatchingEngine(params, cfg, decl, ecfg, bundles=bundles,
                                   policies={"locality": 1, "exact": 1})
    sched = Scheduler(eng)
    rng = np.random.default_rng(61)
    reqs = [Request(rid=i, policy=pol,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=int(rng.integers(3, 7))),
                    max_new=int(rng.integers(4, 13)))
            for i, pol in enumerate(["locality", "exact"] * 3)]
    for r in reqs:
        sched.submit(r)
    finished = sched.run()
    _check_all((cfg, params, decl, bundles), ecfg, finished, reqs)
    assert all(v == 1 for v in eng.compile_counts().values()), \
        eng.compile_counts()


# ---------------------------------------------------------------------------
# Deadline preemption across policy groups
# ---------------------------------------------------------------------------


def test_preemption_across_groups_token_identical(stack):
    """Deadline preemption in a two-group engine: full-budget
    low-priority requests occupy every slot, then an urgent already-late
    request forces an eviction.  The victim requeues as a continuation
    and restarts — and every finished stream (victim included) still
    equals its single-policy reference."""
    cfg, params, dec, bundles = stack
    # max_prompt_len leaves room for prompt + committed tokens: a victim is
    # only feasible while its continuation still fits the admission shape
    ecfg = EngineConfig(num_slots=2, max_prompt_len=24, max_new_cap=12)
    eng = ContinuousBatchingEngine(
        params, cfg, dec, ecfg, bundles=bundles,
        policies={"exact": 1, "topk_tree": 1})
    sched = Scheduler(eng)
    rng = np.random.default_rng(47)
    mk = lambda rid, pol, mn, **kw: Request(  # noqa: E731
        rid=rid, policy=pol, max_new=mn,
        prompt=rng.integers(0, cfg.vocab_size,
                            size=int(rng.integers(3, 7))), **kw)
    low = [mk(0, "exact", 12), mk(1, "topk_tree", 12)]
    for r in low:
        sched.submit(r)
    for _ in range(64):                      # tick until both are admitted
        if not eng.free_slots():
            break
        sched.step()
    assert not eng.free_slots(), "low-priority fill never admitted"
    urgent = mk(2, "exact", 4, priority=1, deadline=time.monotonic())
    sched.submit(urgent)
    finished = sched.run()
    assert sched.preemptions >= 1
    _check_all(stack, ecfg, finished, low + [urgent])


# ---------------------------------------------------------------------------
# Paged KV cache backend: token identity with the dense references
# ---------------------------------------------------------------------------


def test_paged_engine_token_identical_across_policies(stack):
    """The paged KV cache is a pure layout change: the 5-policy mixed
    engine with ``cache_backend="paged"`` — page allocation at admission,
    CoW prefix sharing, release on harvest, all interleaved mid-flight —
    produces exactly the tokens of the dense single-policy reference runs
    for every request."""
    cfg, params, dec, bundles = stack
    decp = dec.replace(cache_backend="paged", page_size=8)
    ecfg = EngineConfig(num_slots=len(MIX), max_prompt_len=6, max_new_cap=12)
    eng = ContinuousBatchingEngine(params, cfg, decp, ecfg, bundles=bundles,
                                   policies={p: 1 for p in MIX})
    sched = Scheduler(eng)
    reqs = _workload(cfg, ecfg)
    for r in reqs:
        sched.submit(r)
    finished = sched.run()
    _check_all(stack, ecfg, finished, reqs)
    assert all(v == 1 for v in eng.compile_counts().values()), \
        eng.compile_counts()
    # after the drain every group's pool is fully released and consistent
    for g in eng.groups:
        assert g.pages is not None, g.name
        g.pages.check_invariants()
        assert g.pages.live_pages() == 0, g.name
        assert g.pages.available_pages() == g.pages.num_pages - 1, g.name


def test_paged_engine_shares_identical_prefixes(stack):
    """Two requests with the same prompt map the prompt-covering page once
    (CoW) and still decode exactly like the dense reference."""
    cfg, params, dec, bundles = stack
    decp = dec.replace(cache_backend="paged", page_size=8)
    # prompt spans exactly one page: max_prompt_len == page_size
    ecfg = EngineConfig(num_slots=2, max_prompt_len=8, max_new_cap=12)
    rng = np.random.default_rng(23)
    prompt = rng.integers(0, cfg.vocab_size, size=8)
    reqs = [Request(rid=i, prompt=prompt.copy(), max_new=6 + 3 * i)
            for i in range(2)]
    eng = ContinuousBatchingEngine(params, cfg, decp, ecfg, bundles=bundles)
    for r in reqs:
        eng.admit(r)
    alloc = eng.groups[0].pages
    pages = {s: list(p) for s, p in alloc.slot_pages.items()}
    assert pages[0][0] == pages[1][0], "prefix page not shared"
    assert alloc.refcount[pages[0][0]] == 2
    done = []
    while eng.has_active():
        done += eng.step()
    _check_all(stack, ecfg, done, reqs)
    alloc.check_invariants()
    # the shared prefix stays cached for future hits after release
    assert len(alloc.prefix_map) >= 1 and alloc.live_pages() == 0


# ---------------------------------------------------------------------------
# Fused verification fast path (DecodeConfig.fused_verify) + tree + carry-over
# ---------------------------------------------------------------------------


def _decode_once(stack, pol, *, fused=False, policy_obj=None, mesh=None,
                 seed=31, max_new=12):
    cfg, params, dec, bundles = stack
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab_size, size=(3, 5))
    d = dec.replace(max_new_tokens=max_new, fused_verify=fused)
    sess = DecodeSession(params, cfg, d, policy=policy_obj or pol, mesh=mesh,
                         bundles=bundles if pol == "draft_model" else None)
    batch = {"tokens": jnp.asarray(prompts)}
    if pol == "input_copy":
        batch["src"] = jnp.asarray(prompts)
    out, stats = sess.decode(batch)
    return np.asarray(out), np.asarray(stats["iterations"])


@pytest.mark.parametrize("pol", ["exact", "topk", "distance", "input_copy",
                                 "topk_tree", "draft_model"])
def test_fused_verify_token_identical(stack, pol):
    """The one-pass Pallas accept kernel (fused_verify=True) is a drop-in:
    tokens AND iteration counts match the unfused acceptor path for every
    policy, including tree verification and the draft-model drafter."""
    out0, it0 = _decode_once(stack, pol, fused=False)
    out1, it1 = _decode_once(stack, pol, fused=True)
    np.testing.assert_array_equal(out0, out1)
    np.testing.assert_array_equal(it0, it1)


def test_tree_verification_lossless(stack):
    """Tree verification commits exactly the greedy stream: topk_tree
    tokens == exact tokens (drafters move iteration counts, never tokens
    under exact acceptance — now across a branching candidate tree)."""
    out_exact, _ = _decode_once(stack, "exact")
    out_tree, _ = _decode_once(stack, "topk_tree")
    np.testing.assert_array_equal(out_tree, out_exact)


def test_draft_carry_over_token_identical_fewer_steps(stack):
    """Suffix carry-over folds the catch-up token into the first draft
    extension: token-identical to the legacy k-step draft loop with
    strictly fewer sequential draft-model forwards."""
    from repro.core import decode as D
    from repro.core import policy as policy_lib

    cfg, params, dec, bundles = stack
    dcfg = bundles["draft"].cfg
    calls = {"n": 0}

    def counting_factory(c, kv_chunk):
        be = D.causal_lm_backend(c, kv_chunk=kv_chunk)
        inner = be.decode_block

        def counted(p, h, caches, ln, tree=None):
            calls["n"] += 1
            return inner(p, h, caches, ln)

        return be._replace(decode_block=counted)

    rng = np.random.default_rng(37)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 5)))}
    d = dec.replace(max_new_tokens=12, policy="draft_model")

    def run(carry):
        pol = policy_lib.resolve_policy(d)
        pol = dataclasses.replace(
            pol, drafter=dataclasses.replace(pol.drafter, carry_over=carry))
        b = {"draft": ModelBundle(bundles["draft"].params, dcfg,
                                  backend_factory=counting_factory)}
        calls["n"] = 0
        with jax.disable_jit():   # count real calls, not traces
            toks, stats = D.bpd_decode(params, cfg, d, batch, policy=pol,
                                       bundles=b)
        return np.asarray(toks), int(stats["iterations"]), calls["n"]

    t_new, it_new, n_new = run(True)
    t_old, it_old, n_old = run(False)
    np.testing.assert_array_equal(t_new, t_old)
    assert it_new == it_old
    assert n_new < n_old, (n_new, n_old)
    # per-iteration: k-1 vs k sequential draft forwards
    from repro.core.draft import DraftModelDrafter

    drafter = DraftModelDrafter()
    k = d.block_k
    assert drafter.draft_steps_per_iter(k) == k - 1
    assert dataclasses.replace(
        drafter, carry_over=False).draft_steps_per_iter(k) == k


# ---------------------------------------------------------------------------
# Sharded variant (CI `sharded` job; skips on 1-device hosts)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 4:
        pytest.skip("needs >=4 host devices: run with XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8")
    from repro.launch.mesh import make_host_mesh

    return make_host_mesh(data=2, model=2, require=True)


@pytest.mark.sharded
def test_mixed_policy_engine_sharded_token_identical(stack, mesh):
    """The mixed-policy engine on a 2×2 ("data", "model") mesh: each
    group's slot view shards the data axis on its own (2 slots / group),
    and every request still matches its single-device single-policy
    reference byte-for-byte."""
    cfg, params, dec, bundles = stack
    ecfg = EngineConfig(num_slots=4, max_prompt_len=6, max_new_cap=12)
    eng = ContinuousBatchingEngine(
        params, cfg, dec, ecfg, mesh=mesh, bundles=bundles,
        policies={"exact": 2, "topk_tree": 2})
    rng = np.random.default_rng(13)
    reqs = []
    for i, pol in enumerate(["exact", "topk_tree", "exact", "topk_tree",
                             "exact", "topk_tree"]):
        reqs.append(Request(
            rid=i, policy=pol,
            prompt=rng.integers(0, cfg.vocab_size,
                                size=int(rng.integers(3, 7))),
            max_new=int(rng.integers(4, 13))))
    done = []
    eng.admit(reqs[0])
    done += eng.step()                      # mid-flight across groups
    for r in reqs[1:4]:
        eng.admit(r)
    while len(done) < 2:
        done += eng.step()
    for r in reqs[4:]:                      # re-admission into freed slots
        while not eng.free_slots(r.policy):
            done += eng.step()
        eng.admit(r)
    while eng.has_active():
        done += eng.step()
    _check_all(stack, ecfg, done, reqs)
    assert all(v == 1 for v in eng.compile_counts().values()), \
        eng.compile_counts()
    # per-group slot views genuinely shard: data over slots, model over kv
    for g in eng.groups:
        k = g.state.caches[0]["attn"]["k"]
        axes = {a for e in k.sharding.spec if e
                for a in (e if isinstance(e, tuple) else (e,))}
        assert {"data", "model"} <= axes, (g.name, k.sharding)


@pytest.mark.sharded
def test_locality_engine_sharded_token_identical(stack, mesh):
    """The locality group's grid-buffer drafter state (B, n+k) and schedule
    position counter shard over the data axis like any slot-leading state:
    the 2×2 mesh run matches the single-device single-policy references."""
    cfg, params, decl, bundles = _locality_stack(stack)
    ecfg = EngineConfig(num_slots=4, max_prompt_len=6, max_new_cap=12)
    eng = ContinuousBatchingEngine(
        params, cfg, decl, ecfg, mesh=mesh, bundles=bundles,
        policies={"locality": 2, "exact": 2})
    sched = Scheduler(eng)
    rng = np.random.default_rng(71)
    reqs = [Request(rid=i, policy=pol,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=int(rng.integers(3, 7))),
                    max_new=int(rng.integers(4, 13)))
            for i, pol in enumerate(["locality", "exact"] * 3)]
    for r in reqs:
        sched.submit(r)
    _check_all((cfg, params, decl, bundles), ecfg, sched.run(), reqs)


@pytest.mark.sharded
def test_paged_engine_sharded_token_identical(stack, mesh):
    """Paged backend on the 2×2 ("data", "model") mesh: the page pool is
    replicated over data (shared across rows) with kv heads over the model
    axis, block tables shard with the slots — and every request still
    matches its dense single-device single-policy reference."""
    cfg, params, dec, bundles = stack
    decp = dec.replace(cache_backend="paged", page_size=8)
    ecfg = EngineConfig(num_slots=4, max_prompt_len=6, max_new_cap=12)
    eng = ContinuousBatchingEngine(
        params, cfg, decp, ecfg, mesh=mesh, bundles=bundles,
        policies={"exact": 2, "topk_tree": 2})
    rng = np.random.default_rng(29)
    reqs = [Request(rid=i, policy=pol,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=int(rng.integers(3, 7))),
                    max_new=int(rng.integers(4, 13)))
            for i, pol in enumerate(["exact", "topk_tree"] * 3)]
    sched = Scheduler(eng)
    for r in reqs:
        sched.submit(r)
    finished = sched.run()
    _check_all(stack, ecfg, finished, reqs)
    for g in eng.groups:
        tbl = g.state.caches[0]["attn"]["tbl"]
        assert any(e for e in tbl.sharding.spec), (g.name, tbl.sharding)
        g.pages.check_invariants()
        assert g.pages.live_pages() == 0, g.name


@pytest.mark.sharded
@pytest.mark.parametrize("pol", ["exact", "topk", "topk_tree"])
def test_fused_verify_sharded_token_identical(stack, mesh, pol):
    """fused_verify on a 2×2 ("data", "model") mesh: the Pallas accept
    kernel (interpret mode on host devices) under GSPMD still matches the
    unfused single-device decode byte-for-byte."""
    out0, it0 = _decode_once(stack, pol, fused=False)
    out1, it1 = _decode_once(stack, pol, fused=True, mesh=mesh)
    np.testing.assert_array_equal(out0, out1)
    np.testing.assert_array_equal(it0, it1)


@pytest.mark.sharded
def test_tree_verification_sharded_lossless(stack, mesh):
    """Tree verification on the 2×2 mesh == single-device exact tokens."""
    out_exact, _ = _decode_once(stack, "exact")
    out_tree, _ = _decode_once(stack, "topk_tree", mesh=mesh)
    np.testing.assert_array_equal(out_tree, out_exact)


@pytest.mark.sharded
def test_group_mesh_divisibility(stack, mesh):
    """Each group's slot view must divide the data axes on its own."""
    cfg, params, dec, _ = stack
    ecfg = EngineConfig(num_slots=4, max_prompt_len=6, max_new_cap=12)
    with pytest.raises(ValueError, match="divisible"):
        ContinuousBatchingEngine(params, cfg, dec, ecfg, mesh=mesh,
                                 policies={"exact": 3, "topk_tree": 1})


# ---------------------------------------------------------------------------
# Pod mesh ("pod", "data", "model"): serving at cluster shape
# (CI `sharded` job with 8 forced host devices; skips elsewhere)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def pod_mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs >=8 host devices: run with XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8")
    from repro.launch.mesh import make_host_mesh

    return make_host_mesh(data=2, model=2, pod=2, require=True)


@pytest.mark.sharded
def test_unified_pod_mesh_token_identical(stack, pod_mesh):
    """The engine on the (2,2,2) ("pod","data","model") mesh: the slot
    slab shards over pod×data and the model over kv heads, and every
    stream still equals its SINGLE-DEVICE single-policy reference — the
    pod mesh moves bytes, never tokens."""
    cfg, params, dec, bundles = stack
    ecfg = EngineConfig(num_slots=8, max_prompt_len=6, max_new_cap=12)
    eng = ContinuousBatchingEngine(
        params, cfg, dec, ecfg, mesh=pod_mesh, bundles=bundles,
        policies={"exact": 4, "topk_tree": 4})
    sched = Scheduler(eng)
    rng = np.random.default_rng(59)
    reqs = [Request(rid=i, policy=pol,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=int(rng.integers(3, 7))),
                    max_new=int(rng.integers(4, 13)))
            for i, pol in enumerate(["exact", "topk_tree"] * 5)]
    for r in reqs:
        sched.submit(r)
    _check_all(stack, ecfg, sched.run(), reqs)
    assert all(v == 1 for v in eng.compile_counts().values()), \
        eng.compile_counts()
    # the slot slab genuinely spans the pod axis (pod×data over slots,
    # model over kv heads) — the cluster shape, not a degenerate layout
    for g in eng.groups:
        k = g.state.caches[0]["attn"]["k"]
        axes = {a for e in k.sharding.spec if e
                for a in (e if isinstance(e, tuple) else (e,))}
        assert {"pod", "data", "model"} <= axes, (g.name, k.sharding)
