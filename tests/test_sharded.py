"""Mesh-sharded decode sessions: token-identical to the single-device paths.

Runs on a forced multi-device host:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        pytest -q tests/test_sharded.py

On a plain 1-device host every test skips (the mesh fixture checks the
device count at runtime), so the tier-1 command stays environment-agnostic.

Coverage: DecodeSession-backed bpd_decode / greedy_decode /
bpd_decode_seq2seq and the continuous-batching engine under mid-flight
admission, all on a ("data", "model") = (2, 2) mesh, asserting

  * outputs byte-identical to the unsharded reference paths,
  * param and KV-cache shardings genuinely split on the model axis
    (not silently replicated),
  * compile-once device functions survive sharding,
  * EngineConfig mesh validation (num_slots % data-axis product).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_dense, tiny_seq2seq
from repro.config import DecodeConfig, get_config
from repro.core import decode as D
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.models import seq2seq as S
from repro.serving import (
    ContinuousBatchingEngine,
    DecodeSession,
    EngineConfig,
    Request,
)

pytestmark = pytest.mark.sharded


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 4:
        pytest.skip("needs >=4 host devices: run with XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8")
    return make_host_mesh(data=2, model=2, require=True)


@pytest.fixture(scope="module")
def dense():
    cfg = tiny_dense()
    params = M.init(jax.random.PRNGKey(0), cfg)
    dec = DecodeConfig(max_new_tokens=16, block_k=4)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 6), 0,
                                          cfg.vocab_size)}
    return cfg, params, dec, batch


@pytest.fixture(scope="module")
def session(mesh, dense):
    cfg, params, dec, _ = dense
    return DecodeSession(params, cfg, dec, mesh=mesh)


def _spec_axes(sharding):
    out = set()
    for entry in sharding.spec:
        if entry is None:
            continue
        for ax in (entry if isinstance(entry, tuple) else (entry,)):
            out.add(ax)
    return out


def test_params_sharded_on_model_axis(session):
    """device_put params actually split on the model axis — the Megatron
    scheme is live, not silently replicated by a divisibility fallback."""
    leaves = jax.tree_util.tree_leaves_with_path(session.params)
    model_sharded = [jax.tree_util.keystr(k) for k, v in leaves
                     if "model" in _spec_axes(v.sharding)]
    assert len(model_sharded) >= 4, model_sharded
    # attention projections are the canonical tensor-parallel weights
    assert any("attn" in name for name in model_sharded)
    for _, v in leaves:
        assert v.sharding.mesh.shape == session.mesh.shape


def test_init_params_draws_into_param_shardings(mesh, dense):
    """Jitted init lands each leaf in its ``param_shardings`` entry (no
    whole copy on one device first) with the eager init's values."""
    from repro.sharding import param_shardings

    cfg, params, _, _ = dense
    placed = M.init_params(jax.random.PRNGKey(0), cfg, mesh)
    want = param_shardings(params, mesh)
    for got, ref, sh in zip(jax.tree_util.tree_leaves(placed),
                            jax.tree_util.tree_leaves(params),
                            jax.tree_util.tree_leaves(want)):
        assert got.sharding == sh
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-6, atol=1e-7)


def test_bpd_decode_token_identical(session, dense):
    cfg, params, dec, batch = dense
    ref_toks, ref_stats = D.bpd_decode(params, cfg, dec, batch)
    toks, stats = D.bpd_decode(params, cfg, dec, batch, session=session)
    np.testing.assert_array_equal(np.asarray(ref_toks), np.asarray(toks))
    np.testing.assert_array_equal(np.asarray(ref_stats["generated"]),
                                  np.asarray(stats["generated"]))
    np.testing.assert_array_equal(np.asarray(ref_stats["text_len"]),
                                  np.asarray(stats["text_len"]))
    # outputs stay data-sharded — the session's explicit out_shardings
    assert "data" in _spec_axes(toks.sharding)


def test_bpd_decode_per_row_budgets_token_identical(session, dense):
    cfg, params, dec, batch = dense
    budgets = jnp.asarray([3, 16, 9, 5], jnp.int32)
    ref, _ = D.bpd_decode(params, cfg, dec, batch, max_new_rows=budgets)
    out, stats = session.decode(batch, max_new_rows=budgets)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    np.testing.assert_array_equal(np.asarray(stats["generated"]),
                                  np.asarray(budgets))


def test_greedy_token_identical(session, dense):
    cfg, params, dec, batch = dense
    ref, _ = D.greedy_decode(params, cfg, dec, batch)
    out, _ = D.greedy_decode(params, cfg, dec, batch, session=session)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


@pytest.mark.parametrize("dec_kw", [
    dict(criterion="topk", top_k=2),       # legacy string aliases …
    dict(criterion="distance", epsilon=2.0),
    dict(policy="adaptive"),               # … and policy-native names
    dict(policy="topk_tree"),
])
def test_policies_token_identical_sharded(mesh, dense, dec_kw):
    """Every criterion alias / registered policy decodes token-identically
    through a mesh-backed session (policy state sharded with the loop)."""
    cfg, params, dec, batch = dense
    d = dec.replace(**dec_kw)
    ref_t, ref_s = D.bpd_decode(params, cfg, d, batch)
    out_t, out_s = D.bpd_decode(params, cfg, d, batch, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(ref_t), np.asarray(out_t))
    np.testing.assert_array_equal(np.asarray(ref_s["generated"]),
                                  np.asarray(out_s["generated"]))
    assert int(ref_s["iterations"]) == int(out_s["iterations"])


def test_draft_model_policy_sharded(mesh, dense):
    """The speculative draft-model policy — a second ModelBundle with its
    own params, shardings, and loop-carried KV cache inside policy_state —
    decodes token-identically through a mesh-backed session, and its draft
    cache genuinely shards (data over slots, kv-heads over model)."""
    from repro.core.bundle import ModelBundle
    from repro.config import ModelConfig

    cfg, params, dec, batch = dense
    dcfg = ModelConfig(name="tiny-draft", num_layers=1, d_model=32,
                       num_heads=2, num_kv_heads=2, d_ff=64,
                       vocab_size=cfg.vocab_size, bpd_enabled=False,
                       max_seq_len=512, dtype="float32")
    dparams = M.init(jax.random.PRNGKey(9), dcfg)
    bundles = {"draft": ModelBundle(dparams, dcfg)}

    ref_t, ref_s = D.bpd_decode(params, cfg, dec, batch,
                                policy="draft_model", bundles=bundles)
    sess = DecodeSession(params, cfg, dec, mesh=mesh, policy="draft_model",
                         bundles={"draft": ModelBundle(dparams, dcfg)})
    out_t, out_s = sess.decode(batch)
    np.testing.assert_array_equal(np.asarray(ref_t), np.asarray(out_t))
    np.testing.assert_array_equal(np.asarray(ref_s["generated"]),
                                  np.asarray(out_s["generated"]))
    assert int(ref_s["iterations"]) == int(out_s["iterations"])
    # ... and greedy equivalence survives the mesh (exact acceptance)
    greedy_t, _ = D.greedy_decode(params, cfg, dec, batch)
    w = batch["tokens"].shape[1] + dec.max_new_tokens
    np.testing.assert_array_equal(np.asarray(greedy_t[:, :w]),
                                  np.asarray(out_t[:, :w]))
    # the draft bundle's params are mesh-placed like the primary's
    for _, v in jax.tree_util.tree_leaves_with_path(sess.aux_params["draft"]):
        assert v.sharding.mesh.shape == sess.mesh.shape


def test_engine_draft_model_sharded_midflight(mesh, dense):
    """Sharded engine + draft-model policy: admission prefills the draft
    cache (scattered into the slot row), steps run the draft model inside
    the jitted step, outputs match the single-device reference."""
    from repro.core.bundle import ModelBundle
    from repro.config import ModelConfig

    cfg, params, dec, _ = dense
    dcfg = ModelConfig(name="tiny-draft", num_layers=1, d_model=32,
                       num_heads=2, num_kv_heads=2, d_ff=64,
                       vocab_size=cfg.vocab_size, bpd_enabled=False,
                       max_seq_len=512, dtype="float32")
    dparams = M.init(jax.random.PRNGKey(9), dcfg)
    eng = ContinuousBatchingEngine(
        params, cfg, dec,
        EngineConfig(num_slots=4, max_prompt_len=8, max_new_cap=16),
        mesh=mesh, policy="draft_model",
        bundles={"draft": ModelBundle(dparams, dcfg)})

    dk = eng.state.policy_state.drafter["caches"][0]["attn"]["k"]
    assert "data" in _spec_axes(dk.sharding), dk.sharding
    assert "model" in _spec_axes(dk.sharding), dk.sharding

    rng = np.random.default_rng(3)
    p0 = rng.integers(0, cfg.vocab_size, size=8)
    p1 = rng.integers(0, cfg.vocab_size, size=5)
    done = []
    eng.admit(Request(rid=0, prompt=p0, max_new=16))
    for _ in range(2):
        done += eng.step()
    eng.admit(Request(rid=1, prompt=p1, max_new=10))
    while eng.has_active():
        done += eng.step()

    by_rid = {f.rid: f for f in done}
    for rid, prompt, mn in ((0, p0, 16), (1, p1, 10)):
        t, s = D.bpd_decode(
            params, cfg, dec.replace(max_new_tokens=mn),
            {"tokens": jnp.asarray(prompt)[None]},
            policy="draft_model",
            bundles={"draft": ModelBundle(dparams, dcfg)})
        n = int(s["text_len"][0])
        np.testing.assert_array_equal(by_rid[rid].tokens,
                                      np.asarray(t[0, len(prompt):n]))
    assert all(v == 1 for v in eng.compile_counts().values()), \
        eng.compile_counts()


def test_input_copy_policy_sharded_seq2seq(mesh):
    """The source-drafting policy (loop-carried drafter state holding the
    src batch) survives sharding token-identically."""
    cfg = tiny_seq2seq()
    params = S.init(jax.random.PRNGKey(4), cfg)
    dec = DecodeConfig(max_new_tokens=12, block_k=4, policy="input_copy")
    batch = {"src": jax.random.randint(jax.random.PRNGKey(5), (2, 6), 1,
                                       cfg.vocab_size)}
    ref, ref_s = D.bpd_decode_seq2seq(params, cfg, dec, batch)
    out, out_s = D.bpd_decode_seq2seq(params, cfg, dec, batch, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    assert int(ref_s["iterations"]) == int(out_s["iterations"])


def test_seq2seq_token_identical(mesh):
    cfg = tiny_seq2seq()
    params = S.init(jax.random.PRNGKey(2), cfg)
    dec = DecodeConfig(max_new_tokens=12, block_k=4)
    batch = {"src": jax.random.randint(jax.random.PRNGKey(3), (2, 5), 0,
                                       cfg.vocab_size)}
    ref, ref_stats = D.bpd_decode_seq2seq(params, cfg, dec, batch)
    out, stats = D.bpd_decode_seq2seq(params, cfg, dec, batch, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    np.testing.assert_array_equal(np.asarray(ref_stats["generated"]),
                                  np.asarray(stats["generated"]))


def _reference(params, cfg, dec, prompt, max_new):
    d1 = dec.replace(max_new_tokens=max_new)
    t, s = D.bpd_decode(params, cfg, d1, {"tokens": jnp.asarray(prompt)[None]})
    return np.asarray(t[0, len(prompt):int(s["text_len"][0])])


def test_engine_sharded_midflight_admission(mesh, dense):
    """The sharded engine serves the same tokens as the single-device
    reference, including a request admitted while another is mid-decode,
    and its slot KV caches genuinely shard on the model axis."""
    cfg, params, dec, _ = dense
    eng = ContinuousBatchingEngine(
        params, cfg, dec,
        EngineConfig(num_slots=4, max_prompt_len=8, max_new_cap=16),
        mesh=mesh)

    k = eng.state.caches[0]["attn"]["k"]
    assert "model" in _spec_axes(k.sharding), k.sharding
    assert "data" in _spec_axes(k.sharding), k.sharding

    rng = np.random.default_rng(3)
    p0 = rng.integers(0, cfg.vocab_size, size=8)
    p1 = rng.integers(0, cfg.vocab_size, size=5)
    done = []
    eng.admit(Request(rid=0, prompt=p0, max_new=16))
    for _ in range(2):                      # progress request 0 first
        done += eng.step()
    eng.admit(Request(rid=1, prompt=p1, max_new=10))
    while eng.has_active():
        done += eng.step()

    by_rid = {f.rid: f for f in done}
    np.testing.assert_array_equal(by_rid[0].tokens,
                                  _reference(params, cfg, dec, p0, 16))
    np.testing.assert_array_equal(by_rid[1].tokens,
                                  _reference(params, cfg, dec, p1, 10))
    # compile-once survives sharding (admit twice, step many, evict twice)
    assert all(v == 1 for v in eng.compile_counts().values()), \
        eng.compile_counts()


def test_engine_sharded_latent_attention_moe(mesh):
    """DeepSeek-V2's smoke model (latent cache, routed and shared experts,
    a dense first layer) serves the same tokens on the (2, 2) mesh as on
    one device, a mid-flight admission included; its per-head MLA
    up-projections split on the model axis, its latent cache on data."""
    cfg = get_config("deepseek-v2-lite", smoke=True).replace(dtype="float32")
    params = M.init(jax.random.PRNGKey(0), cfg)
    dec = DecodeConfig(max_new_tokens=12, block_k=4)
    rng = np.random.default_rng(5)
    p0 = rng.integers(0, cfg.vocab_size, size=8)
    p1 = rng.integers(0, cfg.vocab_size, size=5)

    def serve(mesh_):
        eng = ContinuousBatchingEngine(
            params, cfg, dec,
            EngineConfig(num_slots=4, max_prompt_len=8, max_new_cap=12),
            mesh=mesh_)
        done = []
        eng.admit(Request(rid=0, prompt=p0, max_new=12))
        for _ in range(2):
            done += eng.step()
        eng.admit(Request(rid=1, prompt=p1, max_new=10))
        while eng.has_active():
            done += eng.step()
        return eng, {f.rid: np.asarray(f.tokens) for f in done}

    eng, sharded = serve(mesh)
    _, local = serve(None)
    assert sorted(sharded) == [0, 1]
    for rid in (0, 1):
        np.testing.assert_array_equal(sharded[rid], local[rid])
    attn = eng.session.params["blocks"][1]["attn"]
    assert "model" in _spec_axes(attn["wkb"].sharding)
    assert "model" in _spec_axes(attn["wvb"].sharding)
    assert "data" in _spec_axes(eng.state.caches[1]["attn"]["ckv"].sharding)


def test_engine_config_mesh_validation(mesh, dense):
    cfg, params, dec, _ = dense
    with pytest.raises(ValueError, match="divisible"):
        ContinuousBatchingEngine(
            params, cfg, dec,
            EngineConfig(num_slots=3, max_prompt_len=8, max_new_cap=16),
            mesh=mesh)


def test_engine_config_validation_is_mesh_independent(dense):
    """Construction-time EngineConfig checks fire without a mesh too."""
    cfg, params, dec, _ = dense
    for bad in (EngineConfig(num_slots=0),
                EngineConfig(max_prompt_len=0),
                EngineConfig(max_new_cap=0),
                EngineConfig(max_new_cap=dec.max_new_tokens + 1)):
        with pytest.raises(ValueError):
            ContinuousBatchingEngine(params, cfg, dec, bad)
