"""The paper's central guarantee (§3): blockwise parallel decoding with
exact-match verification produces the SAME output as greedy decoding, for
any block size k, any architecture family, any prompt.

Property-tested with hypothesis over random model seeds / prompts / k, plus
deterministic cases for EOS handling and per-row divergence.
"""
import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st
from conftest import FAMILY_CONFIGS, tiny_seq2seq
from repro.config import DecodeConfig
from repro.core import decode as D
from repro.core import policy as P
from repro.core.bundle import ModelBundle
from repro.core.draft import DraftModelDrafter
from repro.models import model as M
from repro.models import seq2seq as S


def _decode_pair(cfg, seed, b, prompt_len, max_new, k, eos=-1):
    params = M.init(jax.random.PRNGKey(seed), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(seed + 1),
                                          (b, prompt_len), 0, cfg.vocab_size)}
    dec = DecodeConfig(max_new_tokens=max_new, block_k=k, criterion="exact",
                       eos_id=eos)
    bt, bs = D.bpd_decode(params, cfg, dec, batch)
    gt, gs = D.greedy_decode(params, cfg, dec, batch)
    n = prompt_len + max_new
    return (np.asarray(bt[:, :n]), np.asarray(gt[:, :n]),
            np.asarray(bs["text_len"]), np.asarray(gs["text_len"]), bs)


@pytest.mark.slow
@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000), k=st.integers(2, 6),
       family=st.sampled_from(sorted(FAMILY_CONFIGS)))
def test_bpd_equals_greedy_property(seed, k, family):
    cfg = FAMILY_CONFIGS[family](bpd_k=k)
    bt, gt, bl, gl, _ = _decode_pair(cfg, seed, b=2, prompt_len=6, max_new=12, k=k)
    np.testing.assert_array_equal(bl, gl)
    np.testing.assert_array_equal(bt, gt)


@pytest.mark.parametrize("family", sorted(FAMILY_CONFIGS))
def test_bpd_equals_greedy_with_eos(family):
    cfg = FAMILY_CONFIGS[family]()
    # eos inside the vocab: both decoders must stop at the same position
    bt, gt, bl, gl, _ = _decode_pair(cfg, seed=7, b=4, prompt_len=5,
                                     max_new=16, k=4, eos=3)
    np.testing.assert_array_equal(bl, gl)
    for row in range(4):
        n = bl[row]
        np.testing.assert_array_equal(bt[row, :n], gt[row, :n])


def test_bpd_uses_fewer_iterations_than_greedy_on_repetitive_input():
    """A prompt of one repeated token makes the (untrained but deterministic)
    model highly predictable for its own heads is NOT guaranteed; instead we
    check the invocation count never exceeds greedy's."""
    cfg = FAMILY_CONFIGS["dense"]()
    params = M.init(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.zeros((2, 6), jnp.int32)}
    dec = DecodeConfig(max_new_tokens=20, block_k=4)
    _, bs = D.bpd_decode(params, cfg, dec, batch)
    assert int(bs["iterations"]) <= 20
    assert float(bs["mean_accepted"]) >= 1.0


def test_seq2seq_bpd_equals_greedy():
    cfg = tiny_seq2seq()
    params = S.init(jax.random.PRNGKey(3), cfg)
    batch = {"src": jax.random.randint(jax.random.PRNGKey(4), (3, 9), 1,
                                       cfg.vocab_size)}
    dec = DecodeConfig(max_new_tokens=14, criterion="exact", eos_id=1)
    bt, bs = D.bpd_decode_seq2seq(params, cfg, dec, batch)
    gt, gs = D.greedy_decode_seq2seq(params, cfg, dec, batch)
    bl, gl = np.asarray(bs["text_len"]), np.asarray(gs["text_len"])
    np.testing.assert_array_equal(bl, gl)
    for row in range(3):
        n = bl[row] - 1  # text_len includes BOS; outputs are BOS-stripped
        np.testing.assert_array_equal(np.asarray(bt)[row, :n],
                                      np.asarray(gt)[row, :n])


def test_vlm_prefix_bpd_equals_greedy():
    cfg = FAMILY_CONFIGS["dense"](modality="vision_text")
    params = M.init(jax.random.PRNGKey(0), cfg)
    patches = jax.random.normal(jax.random.PRNGKey(1), (2, 6, cfg.d_model))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(2), (2, 5), 0,
                                          cfg.vocab_size),
             "patch_embeds": patches}
    dec = DecodeConfig(max_new_tokens=10, block_k=4)
    bt, _ = D.bpd_decode(params, cfg, dec, batch)
    gt, _ = D.greedy_decode(params, cfg, dec, batch)
    np.testing.assert_array_equal(np.asarray(bt[:, :15]), np.asarray(gt[:, :15]))


def test_rows_advance_independently():
    """Different rows accept different k̂ per iteration; all still match
    their own greedy decode (checked above) and generated counts hit max."""
    cfg = FAMILY_CONFIGS["dense"]()
    params = M.init(jax.random.PRNGKey(11), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(12), (6, 4), 0,
                                          cfg.vocab_size)}
    dec = DecodeConfig(max_new_tokens=12, block_k=4)
    _, stats = D.bpd_decode(params, cfg, dec, batch)
    assert np.all(np.asarray(stats["generated"]) == 12)


def test_approximate_criteria_accept_at_least_exact():
    cfg = FAMILY_CONFIGS["dense"]()
    params = M.init(jax.random.PRNGKey(5), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(6), (4, 6), 0,
                                          cfg.vocab_size)}
    means = {}
    for crit, kw in [("exact", {}), ("topk", dict(top_k=3)),
                     ("distance", dict(epsilon=5.0))]:
        dec = DecodeConfig(max_new_tokens=24, block_k=4, criterion=crit, **kw)
        _, stats = D.bpd_decode(params, cfg, dec, batch)
        means[crit] = float(stats["mean_accepted"])
    assert means["topk"] >= means["exact"] - 1e-6
    assert means["distance"] >= 1.0


# ---------------------------------------------------------------------------
# Verify first, then the heads at the accepted slot only: the same decode as
# projecting every head at every block position.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _Recorder(P.Drafter):
    """Wraps a drafter: hands it ``DraftInputs.logits`` (the step's own, or
    ``swap(inputs)``) and keeps a copy of each, with the slot."""

    inner: P.Drafter = None
    seen: list = dataclasses.field(default=None, compare=False)
    swap: Optional[Callable] = None

    def tree_topology(self, block_k):
        return self.inner.tree_topology(block_k)

    def draft(self, inputs, state):
        if self.swap is not None:
            inputs = inputs._replace(logits=self.swap(inputs))
        self.seen.append((np.asarray(inputs.logits),
                          np.asarray(inputs.slot)))
        return self.inner.draft(inputs, state)


def _recorded_step(step, seen):
    def recorded(params, cfg, dec, backend, state, *, policy=None, **kw):
        pol = P.resolve_policy(dec, policy)
        pol = dataclasses.replace(pol, drafter=_Recorder(pol.drafter, seen))
        return step(params, cfg, dec, backend, state, policy=pol, **kw)

    return recorded


def _reference_step(step, seen):
    """The step as it computed the heads before: every head's logits at
    every block position (``head_logits`` over (B, k, d)), p_1 sliced out
    of them to verify, and the drafter handed them gathered at the
    accepted slot."""
    def reference(params, cfg, dec, backend, state, *, policy=None, **kw):
        block_k = dec.block_k or cfg.bpd_k
        every = {}

        def p1_logits(p, hidden):
            every["logits"] = backend.head_logits(p, hidden)[:, :, :block_k]
            return every["logits"][:, :, 0]

        def at_slot(inputs):
            idx = inputs.slot[:, None, None, None]
            return jnp.take_along_axis(every["logits"], idx, axis=1)[:, 0]

        pol = P.resolve_policy(dec, policy)
        pol = dataclasses.replace(
            pol, drafter=_Recorder(pol.drafter, seen, at_slot))
        return step(params, cfg, dec, backend._replace(p1_logits=p1_logits),
                    state, policy=pol, **kw)

    return reference


def _drafter(name):
    return {"heads": P.HeadsDrafter, "headless": P.HeadsDrafter,
            "topk_tree": lambda: P.TopKTreeDrafter(fanout=2),
            "input_copy": P.InputCopyDrafter,
            "draft_model": DraftModelDrafter}[name]()


def _runner(drafter, identity_p1, block_k):
    """A decode of a few rows under ``drafter``.  Top-64-of-97 acceptance
    accepts about two of three proposals, so the accepted slot moves over
    the block (exact acceptance of an untrained model keeps it at 0)."""
    pol = P.DecodePolicy(_drafter(drafter), P.TopKAcceptor(top_k=64),
                         P.StaticSchedule(), name=drafter)
    dec = DecodeConfig(max_new_tokens=10, block_k=block_k)
    if drafter == "input_copy":
        cfg = tiny_seq2seq(bpd_identity_p1=identity_p1)
        params = S.init(jax.random.PRNGKey(3), cfg)
        batch = {"src": jax.random.randint(jax.random.PRNGKey(4), (3, 9), 1,
                                           cfg.vocab_size)}
        return lambda: D.bpd_decode_seq2seq(params, cfg, dec, batch,
                                            policy=pol)
    cfg = FAMILY_CONFIGS["dense"](bpd_identity_p1=identity_p1,
                                  bpd_enabled=drafter != "headless")
    params = M.init(jax.random.PRNGKey(8), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(9), (3, 5), 0,
                                          cfg.vocab_size)}
    bundles = None
    if drafter == "draft_model":
        dcfg = FAMILY_CONFIGS["dense"](name="tiny-draft", num_layers=1,
                                       d_model=32, bpd_enabled=False)
        bundles = {"draft": ModelBundle(
            M.init(jax.random.PRNGKey(10), dcfg), dcfg)}
    return lambda: D.bpd_decode(params, cfg, dec, batch, policy=pol,
                                bundles=bundles)


VERIFY_FIRST_CASES = [
    pytest.param(d, p1, k, id=f"{d}-{'p1id' if p1 else 'p1ffn'}-k{k}")
    for d in ("heads", "topk_tree", "input_copy", "draft_model")
    for p1 in (True, False) for k in (2, 4)
] + [pytest.param("headless", True, 1, id="headless-k1")]


@pytest.mark.parametrize("drafter,identity_p1,block_k", VERIFY_FIRST_CASES)
def test_verify_first_matches_heads_at_every_position(
        monkeypatch, drafter, identity_p1, block_k):
    """Tokens, lengths and counts are bitwise those of the reference step,
    and the drafter's (B, block_k, V) logits at the accepted slot are the
    reference's every-position logits gathered there.  Under
    ``disable_jit`` the loop runs in Python, so each iteration's logits are
    recorded."""
    run = _runner(drafter, identity_p1, block_k)
    step = D.bpd_iteration
    seen, ref_seen = [], []
    monkeypatch.setattr(D, "bpd_iteration", _recorded_step(step, seen))
    with jax.disable_jit():
        toks, stats = run()
    monkeypatch.setattr(D, "bpd_iteration", _reference_step(step, ref_seen))
    with jax.disable_jit():
        ref_toks, ref_stats = run()
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(ref_toks))
    for key in ("text_len", "generated"):
        np.testing.assert_array_equal(np.asarray(stats[key]),
                                      np.asarray(ref_stats[key]))
    assert len(seen) == len(ref_seen) == int(stats["iterations"])
    for (got, slot), (want, ref_slot) in zip(seen, ref_seen):
        np.testing.assert_array_equal(slot, ref_slot)
        assert got.shape == want.shape == (3, block_k, got.shape[-1])
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    slots = np.concatenate([slot for _, slot in seen])
    assert np.all(slots < block_k) and (block_k == 1 or np.any(slots > 0))


@pytest.mark.parametrize("identity_p1", [True, False],
                         ids=["p1id", "p1ffn"])
def test_step_program_has_no_head_logits_at_every_position(identity_p1):
    """The serving engine's compiled step holds no (B, k, K, V) logits and no
    (B, k, K, dh) head activations, in any flattening: the heads past p_1
    run at the accepted slot alone."""
    from repro.serving import ContinuousBatchingEngine, EngineConfig

    b, k, dh = 3, 4, 96
    cfg = FAMILY_CONFIGS["dense"](bpd_k=k, bpd_hidden=dh,
                                  bpd_identity_p1=identity_p1)
    v = cfg.padded_vocab_size
    eng = ContinuousBatchingEngine(
        M.init(jax.random.PRNGKey(0), cfg), cfg,
        DecodeConfig(max_new_tokens=8, block_k=k),
        EngineConfig(num_slots=b, max_prompt_len=8, max_new_cap=8))
    g = eng.groups[0]
    lowered = g.fns.step.lower(eng.params, eng.aux_params, g.state)
    hlo = lowered.as_text() + lowered.compile().as_text()
    for width in (v, dh):
        for dims in ((b, k, k), (b * k, k), (b, k * k), (b * k * k,)):
            shape = ",".join(map(str, dims + (width,)))
            assert f"[{shape}]" not in hlo, shape
            assert f"<{shape.replace(',', 'x')}x" not in hlo, shape
    # the check sees the shapes the step does hold
    assert f"[{b},{k},{v}]" in hlo and f"[{b},{k - 1},{v}]" in hlo
