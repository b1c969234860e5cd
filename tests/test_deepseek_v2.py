"""DeepSeek-V2 (latent attention over a latent KV cache, DeepSeek routing,
a dense first layer) against the plain float32 reference
(``tests/reference_deepseek_v2.py``) on seeded random weights, at the smoke
size, on the CPU, by logits.

Tolerance: ``LOGIT_TOL`` = 1e-4 on the largest logit difference (and on
the gap of a served token below the reference's best logit).  Both sides
compute in float32 on the same weights: they differ only in summation
order and in the absorbed form's association of the verify step (scores
against W_uk-folded queries, W_uv after the weighted sum), about 4e-6 on
logits of magnitude 4 at this size.  The same model with its trunk in
bfloat16 misses by about 0.5 (``test_bf16_trunk_fails_the_tolerance``),
so the tolerance tells float32 from the next precision down.
"""
import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_deepseek_v2 as R
from repro.config import DecodeConfig, ModelConfig, get_config
from repro.core.bundle import ModelBundle
from repro.models import blocks as B
from repro.models import cache as cache_lib
from repro.models import model as M
from repro.models.attention import mla_softmax_scale
from repro.models.layers import (activation, apply_rope_pairs, dense_apply,
                                 yarn_inv_freq, yarn_mscale)
from repro.models.moe import _assignment_ranks, _expert_ffn, moe_apply, moe_init
from repro.serving import ContinuousBatchingEngine, DecodeSession, \
    EngineConfig, Request
from repro.sharding.policy import maybe_shard_expert

LOGIT_TOL = 1e-4


def _cfg(**kw) -> ModelConfig:
    """The smoke config with three layers (the dense one and two MoE), in
    float32."""
    base = dict(num_layers=3, dtype="float32")
    base.update(kw)
    return get_config("deepseek-v2-lite", smoke=True).replace(**base)


def hf_config(cfg: ModelConfig) -> Dict:
    """The published config.json keys the reference reads."""
    ys = cfg.rope_scaling
    return {
        "hidden_size": cfg.d_model, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "rope_theta": cfg.rope_theta,
        "rope_scaling": (None if ys is None
                         else dict(dataclasses.asdict(ys), type="yarn")),
        "rms_norm_eps": 1e-6, "n_routed_experts": cfg.num_experts,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "n_shared_experts": cfg.num_shared_experts,
        "first_k_dense_replace": cfg.first_k_dense_replace,
    }


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, M.init(jax.random.PRNGKey(0), cfg)


def _tokens(cfg, n, seed):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 0,
                                         cfg.vocab_size))


def _program_logits(params, cfg, toks):
    def fwd(params, toks):
        h = M.embed_inputs(params, cfg, {"tokens": toks[None]})
        h, _, _ = M.forward_hidden(params, cfg, h, moe_full_capacity=True)
        return M.project_vocab(params, cfg, h)[0]
    return np.asarray(jax.jit(fwd)(params, jnp.asarray(toks)), np.float32)


def _reference_logits(params, cfg, toks):
    c = hf_config(cfg)
    return np.asarray(jax.jit(lambda p, t: R.forward(p, c, t))(
        params, jnp.asarray(toks)))


# ---------------------------------------------------------------------------
# The pieces
# ---------------------------------------------------------------------------


def test_yarn_frequencies_and_softmax_scale_closed_form():
    """DeepSeek-V2-Lite's YaRN (factor 40, beta 32/1, original 4096, theta
    1e4, rope dim 64): dims turning more than 32 times over 4096 positions
    keep theta^(-2i/64), those turning less than once are divided by 40,
    a linear ramp between; softmax scale 192^-1/2 (0.1*0.707*ln 40 + 1)^2."""
    cfg = get_config("deepseek-v2-lite")
    dim, theta = 64, 10000.0
    # the correction range in closed form: dim*ln(L/(2*pi*turns))/(2 ln theta)
    low = math.floor(dim * math.log(4096 / (2 * math.pi * 32))
                     / (2 * math.log(theta)))
    high = math.ceil(dim * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(theta)))
    assert (low, high) == (10, 23)
    base = theta ** (-np.arange(0, dim, 2) / dim)
    got = yarn_inv_freq(dim, theta, cfg.rope_scaling)
    np.testing.assert_allclose(got[:low + 1], base[:low + 1], rtol=1e-6)
    np.testing.assert_allclose(got[high:], base[high:] / 40, rtol=1e-6)
    i = np.arange(low + 1, high)
    ramp = (i - low) / (high - low)
    np.testing.assert_allclose(got[low + 1:high],
                               base[i] / 40 * ramp + base[i] * (1 - ramp),
                               rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert math.isclose(mla_softmax_scale(cfg), 192 ** -0.5 * m * m,
                        rel_tol=1e-12)
    # mscale == mscale_all_dim: cos and sin are not rescaled
    assert yarn_mscale(40, 0.707) / yarn_mscale(40, 0.707) == 1.0


def test_rope_rotates_interleaved_pairs():
    """The published pairing: dims (2i, 2i+1) rotate together by
    position * freq_i; the result is stored de-interleaved (evens first)."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal((5, 2, 8)).astype(np.float32)       # (S, H, 8)
    freqs = np.array([1.0, 0.5, 0.1, 0.01], np.float32)
    pos = np.arange(5)
    got = np.asarray(apply_rope_pairs(jnp.asarray(x), jnp.asarray(pos),
                                      freqs))
    ang = pos[:, None, None] * freqs[None, None, :]
    ev, od = x[..., 0::2], x[..., 1::2]
    want = np.concatenate([ev * np.cos(ang) - od * np.sin(ang),
                           od * np.cos(ang) + ev * np.sin(ang)], -1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_routing_without_renormalisation_against_hand_written_top6():
    """Softmax top-6 over 8 experts, gates not renormalised but scaled by
    routed_scaling_factor, plus ungated shared experts: against a per-token
    loop written out by hand."""
    cfg = _cfg(num_experts=8, num_experts_per_tok=6,
               routed_scaling_factor=2.5)
    p = B.block_init(jax.random.PRNGKey(3), cfg, 1)["moe"]
    assert "gate" not in p["shared"]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 5, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, x: moe_apply(p, cfg, x,
                                             full_capacity=True)[0])(p, x)
        xs = np.asarray(x, np.float64).reshape(-1, cfg.d_model)
        pn = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), p)

        def ffn(w1, w3, w2, v):
            g = v @ w1
            return ((g / (1 + np.exp(-g))) * (v @ w3)) @ w2

        want = []
        for v in xs:
            z = v @ pn["router"]["w"]
            prob = np.exp(z - z.max()) / np.exp(z - z.max()).sum()
            top = np.argsort(-prob)[:6]
            y = sum(2.5 * prob[e] * ffn(pn["w1"][e], pn["w3"][e], pn["w2"][e],
                                        v) for e in top)
            sh = pn["shared"]
            want.append(y + ffn(sh["w1"]["w"], sh["w3"]["w"], sh["w2"]["w"],
                                v))
    np.testing.assert_allclose(np.asarray(got).reshape(-1, cfg.d_model),
                               np.stack(want), rtol=1e-4, atol=1e-5)


def test_dense_first_layers_then_moe():
    """Layers below first_k_dense_replace hold a dense MLP of dense_d_ff;
    the rest hold the experts."""
    cfg = _cfg(num_layers=4, first_k_dense_replace=2)
    p = jax.eval_shape(lambda: M.init(jax.random.PRNGKey(0), cfg))
    for i, bp in enumerate(p["blocks"]):
        if i < 2:
            assert "moe" not in bp
            assert bp["mlp"]["w1"]["w"].shape == (cfg.d_model, cfg.dense_d_ff)
        else:
            assert "mlp" not in bp
            assert bp["moe"]["w1"].shape == (cfg.num_experts, cfg.d_model,
                                             cfg.d_ff)
    params = M.init(jax.random.PRNGKey(0), cfg)
    toks = _tokens(cfg, 12, 5)
    diff = np.abs(_program_logits(params, cfg, toks)
                  - _reference_logits(params, cfg, toks))
    assert diff.max() < LOGIT_TOL


def test_latent_cache_rows_scatter_reset_commit():
    """The latent layout (ckv, kpe, pos) through the engine's row
    operations: a prefilled row scatters into its slot alone, reset
    invalidates positions only, commit leaves attention caches as they are,
    and memory_bytes counts the latent."""
    cfg = _cfg()
    be = cache_lib.DenseBackend()
    caches = be.init(cfg, 3, 16, 4)
    buf = cache_lib.attn_buf_len(cfg, 0, 16, 4)
    assert {k: v.shape for k, v in caches[0]["attn"].items()} == {
        "ckv": (3, buf, cfg.kv_lora_rank), "kpe": (3, buf, cfg.qk_rope_head_dim),
        "pos": (3, buf)}
    params = M.init(jax.random.PRNGKey(0), cfg)
    row = jax.jit(lambda rc, toks: M.forward_hidden(
        params, cfg, M.embed_inputs(params, cfg, {"tokens": toks[None]}),
        caches=rc, moe_full_capacity=True)[2])(
            be.row_init(cfg, 16, 4), jnp.asarray(_tokens(cfg, 6, 7)))
    full = M.scatter_cache_row(caches, row, 1)
    for lc, rc in zip(full, row):
        a, r = lc["attn"], rc["attn"]
        np.testing.assert_array_equal(a["ckv"][1], r["ckv"][0])
        np.testing.assert_array_equal(a["kpe"][1], r["kpe"][0])
        assert list(np.asarray(a["pos"][1, :7])) == [0, 1, 2, 3, 4, 5, -1]
        assert not np.asarray(a["ckv"][0]).any()
        assert (np.asarray(a["pos"])[[0, 2]] == -1).all()
    reset = M.reset_cache_rows(full, jnp.array([False, True, False]))
    assert (np.asarray(reset[0]["attn"]["pos"][1]) == -1).all()
    np.testing.assert_array_equal(reset[0]["attn"]["ckv"],
                                  full[0]["attn"]["ckv"])
    committed = M.commit_caches(cfg, full, jnp.array([1, 2, 0]))
    for a, b in zip(jax.tree_util.tree_leaves(committed),
                    jax.tree_util.tree_leaves(full)):
        np.testing.assert_array_equal(a, b)
    per_layer = 3 * buf * ((cfg.kv_lora_rank + cfg.qk_rope_head_dim) * 4 + 4)
    assert be.memory_bytes(cfg, 3, 16, 4) == cfg.num_layers * per_layer


def test_paged_backend_refuses_latent_attention():
    cfg = _cfg()
    with pytest.raises(NotImplementedError, match="latent"):
        cache_lib.PagedBackend(page_size=16).init(cfg, 2, 16, 4)
    params = M.init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(NotImplementedError, match="latent"):
        ContinuousBatchingEngine(
            params, cfg, DecodeConfig(max_new_tokens=8, block_k=4,
                                      cache_backend="paged"),
            EngineConfig(num_slots=2, max_prompt_len=8, max_new_cap=8))


def test_tree_and_draft_model_policies_refuse_latent_attention():
    cfg = _cfg()
    params = M.init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(NotImplementedError, match="MLA"):
        DecodeSession(params, cfg, DecodeConfig(max_new_tokens=8, block_k=4,
                                                policy="topk_tree"))
    draft_cfg = get_config("granite-3-8b", smoke=True).replace(
        vocab_size=cfg.vocab_size)
    bundle = ModelBundle(M.init(jax.random.PRNGKey(1), draft_cfg), draft_cfg)
    with pytest.raises(NotImplementedError, match="MLA"):
        DecodeSession(params, cfg, DecodeConfig(max_new_tokens=8, block_k=4,
                                                policy="draft_model"),
                      bundles={"draft": bundle})
    with pytest.raises(NotImplementedError, match="MLA"):
        DecodeSession(bundle.params, draft_cfg,
                      DecodeConfig(max_new_tokens=8, block_k=4,
                                   policy="draft_model"),
                      bundles={"draft": ModelBundle(params, cfg)})


# ---------------------------------------------------------------------------
# Against the reference
# ---------------------------------------------------------------------------


def test_full_forward_matches_reference(model):
    cfg, params = model
    toks = _tokens(cfg, 40, 1)
    diff = np.abs(_program_logits(params, cfg, toks)
                  - _reference_logits(params, cfg, toks))
    assert diff.max() < LOGIT_TOL, diff.max()


def test_bf16_trunk_fails_the_tolerance(model):
    """The control: the same weights with the trunk computing in bfloat16
    miss the reference by far more than LOGIT_TOL."""
    cfg, params = model
    toks = _tokens(cfg, 40, 1)
    low = _program_logits(params, cfg.replace(dtype="bfloat16"), toks)
    diff = np.abs(low - _reference_logits(params, cfg, toks))
    assert diff.max() > 100 * LOGIT_TOL, diff.max()


def test_prefill_then_cached_verify_with_rollback_matches_reference(model):
    """Prefill through the cache, then k-wide verify steps in the absorbed
    form; after the first, roll back to k̂ = 2 (the length says so, the
    stale latents stay) and verify another block over them."""
    cfg, params = model
    k, plen = 4, 9
    prompt = _tokens(cfg, plen, 2)
    block1, block2 = _tokens(cfg, k, 3), _tokens(cfg, k, 4)
    caches = jax.jit(lambda caches, toks: M.forward_hidden(
        params, cfg, M.embed_inputs(params, cfg, {"tokens": toks[None]}),
        caches=caches, moe_full_capacity=True)[2])(
            M.init_caches(cfg, 1, 32, k), jnp.asarray(prompt))

    @jax.jit
    def step(caches, block, length):
        h = M.embed_inputs(params, cfg, {"tokens": block[None]})
        hid, staged = M.decode_block_step(params, cfg, h, caches, length[None])
        staged = M.commit_caches(cfg, staged, jnp.array([k], jnp.int32))
        return M.project_vocab(params, cfg, hid)[0], staged

    def verify(caches, block, length):
        logits, caches = step(caches, jnp.asarray(block),
                              jnp.asarray(length, jnp.int32))
        return np.asarray(logits), caches

    got1, caches = verify(caches, block1, plen)
    want1 = _reference_logits(params, cfg, np.concatenate(
        [prompt, block1]))[plen:]
    assert np.abs(got1 - want1).max() < LOGIT_TOL
    seq = np.concatenate([prompt, block1[:2], block2])
    got2, _ = verify(caches, block2, plen + 2)
    want2 = _reference_logits(params, cfg, seq)[plen + 2:]
    assert np.abs(got2 - want2).max() < LOGIT_TOL


def test_engine_serves_the_reference_greedy_tokens(model):
    """Through ContinuousBatchingEngine (padded admissions, k-wide verify
    steps, a request admitted while another is mid-flight): every served
    token's reference logit lies within LOGIT_TOL of the reference's best
    at its position."""
    cfg, params = model
    eng = ContinuousBatchingEngine(
        params, cfg, DecodeConfig(max_new_tokens=12, block_k=4),
        EngineConfig(num_slots=2, max_prompt_len=8, max_new_cap=12))
    prompts = {0: _tokens(cfg, 8, 11), 1: _tokens(cfg, 5, 12)}
    done = []
    eng.admit(Request(rid=0, prompt=prompts[0], max_new=12))
    for _ in range(2):
        done += eng.step()
    eng.admit(Request(rid=1, prompt=prompts[1], max_new=10))
    while eng.has_active():
        done += eng.step()
    assert sorted(f.rid for f in done) == [0, 1]
    for f in done:
        toks = np.asarray(f.tokens)
        assert len(toks) == (12 if f.rid == 0 else 10)
        n = len(prompts[f.rid])
        logits = _reference_logits(params, cfg, np.concatenate(
            [prompts[f.rid], toks]))[n - 1:n - 1 + len(toks)]
        gap = logits.max(-1) - logits[np.arange(len(toks)), toks]
        assert gap.max() < LOGIT_TOL, (f.rid, gap.max())


# ---------------------------------------------------------------------------
# The other MoE models are unchanged
# ---------------------------------------------------------------------------


def _parent_moe_apply(p, cfg: ModelConfig, x, *, full_capacity: bool = False
                      ) -> Tuple[jnp.ndarray, Dict]:
    b, s, d = x.shape
    e, topk = cfg.num_experts, cfg.num_experts_per_tok
    ep = cfg.padded_num_experts

    logits = dense_apply(p["router"], x.astype(jnp.float32))         # (B, S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, topk)               # (B, S, K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)            # renorm

    if full_capacity:
        capacity = s  # a row's expert gets at most one slot per token
    else:
        capacity = int(max(1, cfg.capacity_factor * topk * s / e))
    capacity = min(capacity, s)

    def dispatch_row(xr, er):
        """xr: (S, d); er: (S, K) -> per-group expert buffer + slots."""
        rank = _assignment_ranks(er.reshape(s * topk)).reshape(s, topk)
        keep = rank < capacity
        # destination in the (Ep*Cg) buffer; capacity overflow -> dump row
        slot = jnp.where(keep, er * capacity + rank, ep * capacity)
        xin = jnp.zeros((ep * capacity + 1, d), xr.dtype)
        xin = xin.at[slot.reshape(-1)].add(
            jnp.broadcast_to(xr[:, None, :], (s, topk, d)).reshape(-1, d))
        return xin[: ep * capacity].reshape(ep, capacity, d), slot, keep

    xin, slot, keep = jax.vmap(dispatch_row)(x, expert_ids)  # (B, Ep, Cg, d)
    xin = maybe_shard_expert(xin)

    xout = _expert_ffn(p, xin, cfg.activation)                # (B, Ep, Cg, d)
    xout = maybe_shard_expert(xout)

    def gather_row(xo, sl, gv, kp):
        flat = jnp.concatenate(
            [xo.reshape(ep * capacity, d), jnp.zeros((1, d), xo.dtype)], 0)
        g = jnp.take(flat, sl.reshape(-1), axis=0).reshape(s, topk, d)
        w = (gv * kp.astype(gv.dtype)).astype(xo.dtype)
        return jnp.einsum("skd,sk->sd", g, w)

    y = jax.vmap(gather_row)(xout, slot, gate_vals, keep)     # (B, S, d)

    if "shared" in p:
        sp = p["shared"]
        h = activation("silu", dense_apply(sp["w1"], x)) * dense_apply(sp["w3"], x)
        shared_out = dense_apply(sp["w2"], h)
        g = jax.nn.sigmoid(dense_apply(sp["gate"], x).astype(jnp.float32)
                           ).astype(x.dtype)
        y = y + g * shared_out

    # Switch-Transformer load-balance loss + router z-loss
    t = b * s
    density = jnp.zeros((e,), jnp.float32).at[expert_ids.reshape(-1)].add(1.0) / (t * topk)
    density_proxy = jnp.mean(probs.reshape(t, -1), axis=0)
    aux_loss = e * jnp.sum(density * density_proxy)
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    dropped = 1.0 - jnp.sum(keep) / (t * topk)

    metrics = {
        "moe_aux_loss": aux_loss.astype(jnp.float32),
        "moe_z_loss": z_loss.astype(jnp.float32),
        "moe_dropped_frac": dropped.astype(jnp.float32),
    }
    return y, metrics

@pytest.mark.parametrize("arch", ["qwen2-moe-a2.7b", "olmoe-1b-7b"])
def test_other_moe_models_bit_identical_to_before(arch, monkeypatch):
    """qwen2-moe (renormalised top-4, sigmoid-gated shared experts) and
    olmoe compute exactly what they did before DeepSeek routing was added:
    their smoke models' logits, bit for bit, against the previous
    ``moe_apply`` (``_parent_moe_apply``, kept verbatim above)."""
    cfg = get_config(arch, smoke=True).replace(dtype="float32")
    params = M.init(jax.random.PRNGKey(0), cfg)
    toks = _tokens(cfg, 16, 9)
    new = _program_logits(params, cfg, toks)
    monkeypatch.setattr(B, "moe_apply", _parent_moe_apply)
    old = _program_logits(params, cfg, toks)
    np.testing.assert_array_equal(new, old)


@pytest.mark.parametrize("arch", ["qwen2-moe-a2.7b", "olmoe-1b-7b"])
def test_gathered_dispatch_bit_identical_when_tokens_drop(arch):
    """The dispatch gathers its buffer where the previous one scatter-added
    into zeros; with half the assignments over capacity (dropped to the
    dump row) the layer's output is the same, bit for bit."""
    cfg = get_config(arch, smoke=True).replace(dtype="float32",
                                               capacity_factor=0.5)
    p = moe_init(jax.random.PRNGKey(1), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 40, cfg.d_model))
    y, m = moe_apply(p, cfg, x)
    y0, _ = _parent_moe_apply(p, cfg, x)
    assert float(m["moe_dropped_frac"]) > 0.25
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y0))


def _per_row_buffer(p, cfg: ModelConfig, x):
    """The previous no-drop computation: each batch row's (Ep, S, d) buffer
    (what ``_parent_moe_apply(full_capacity=True)`` built), through the
    capacity path at a capacity of S.  That path is the parent's, bit for
    bit (``test_gathered_dispatch_bit_identical_when_tokens_drop``)."""
    return moe_apply(p, cfg.replace(capacity_factor=float(cfg.num_experts)),
                     x)


@pytest.mark.parametrize("arch,exact", [("qwen2-moe-a2.7b", False),
                                        ("olmoe-1b-7b", True),
                                        ("deepseek-v2-lite", False)])
def test_sorted_grouped_dispatch_matches_per_row_buffer(arch, exact):
    """The no-drop path's token-sorted grouped products against the per-row
    buffer under skewed routing: expert 0 is in every token's top-k,
    experts 3-5 and the two padded experts get no row, and B·S = 111 (222
    assignments) divides by no row tile.  olmoe's output is the same bit
    for bit.  For qwen2-moe and DeepSeek the CPU's product over a 128-row
    tile sums in another order than its product over a row's 37-slot
    buffer: within 1e-6 of the output's scale, as close to a float64
    reference as the per-row buffer is."""
    cfg = get_config(arch, smoke=True).replace(
        dtype="float32", num_experts=6, expert_pad_multiple=4)
    assert cfg.padded_num_experts == 8
    p = moe_init(jax.random.PRNGKey(1), cfg)
    d = cfg.d_model
    u = jnp.ones((d,), jnp.float32) / math.sqrt(d)
    w = p["router"]["w"] * 0.1
    w = w.at[:, 0].set(10.0 * u).at[:, 3:].set(-10.0 * u[:, None])
    p["router"]["w"] = w
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 37, d)) + 5.0 * u
    ids = np.asarray(jax.lax.top_k(x @ w, cfg.num_experts_per_tok)[1])
    assert (ids == 0).any(-1).all() and not (ids >= 3).any()

    got, m = jax.jit(lambda p, x: moe_apply(p, cfg, x,
                                            full_capacity=True))(p, x)
    want, m0 = jax.jit(lambda p, x: _per_row_buffer(p, cfg, x))(p, x)
    assert float(m["moe_dropped_frac"]) == 0.0
    assert abs(float(m0["moe_dropped_frac"])) < 1e-6
    got, want = np.asarray(got), np.asarray(want)
    if exact:
        np.testing.assert_array_equal(got, want)
    else:
        err = np.abs(got - want).max() / np.abs(want).max()
        assert err <= 1e-6, err
