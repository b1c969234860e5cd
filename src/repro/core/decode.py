"""Blockwise parallel decoding (paper §3–§5) and the greedy baseline.

The combined scoring/proposal formulation (§4) is used throughout: one model
invocation per iteration serves simultaneously as the verification of the
current block and the prediction of the next block, so decoding an output of
length m costs (m / mean-k̂) + 1 invocations instead of m.  Inside that one
invocation the step verifies first, from p_1 alone at the k block
positions, and only then runs the other heads, at the one position the
next block is drafted from (the accepted slot k̂-1): the vocabulary
projection covers B·(k + block_k - 1) rows, not B·k·K.

The loop is a ``jax.lax.while_loop`` with fully static shapes; per-row
accepted block sizes k̂ let every batch row advance at its own rate.

Model-agnostic: a ``Backend`` bundles the embed / decode-block / head-logits
functions, with adapters for the decoder-only CausalLM and the paper's
encoder-decoder MT model.

Placement: every run-to-completion entry point (``bpd_decode``,
``greedy_decode``, ``bpd_decode_seq2seq``) is a thin wrapper over
``repro.serving.session.DecodeSession`` — the one sharding-aware driver
shared with the continuous-batching engine.  With no ``mesh``/``session``
argument the wrappers are trace-transparent (identical to the historical
eager paths, safe under an outer ``jax.jit``); with a mesh they run jitted
with explicit in/out shardings from ``repro.sharding.policy``.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import DecodeConfig, ModelConfig
from repro.core import policy as policy_lib
from repro.core.policy import DecodePolicy, DraftInputs, PolicyState
from repro.models import cache as cache_lib
from repro.models import model as model_lib
from repro.models import seq2seq as seq2seq_lib
from repro.models.layers import embed_apply


class Backend(NamedTuple):
    """Model functions the BPD engine needs.

    A verify step calls ``p1_logits`` at every block position, and
    ``head_logits(params, hidden, 1, block_k)`` (heads p_2..p_block_k) at
    the accepted slot only.
    """

    embed_tokens: Callable          # (params, tokens (B,S)) -> (B,S,d)
    decode_block: Callable          # (params, h, caches, length) -> (hidden, staged_caches)
    commit: Callable                # (caches, khat) -> caches
    head_logits: Callable           # (params, hidden, start=0, stop=None) -> (..., n, V)
    p1_logits: Callable             # (params, hidden) -> (..., V)


def causal_lm_backend(cfg: ModelConfig, *, kv_chunk: int = 0) -> Backend:
    return Backend(
        embed_tokens=lambda p, t: embed_apply(p["embed"], t).astype(cfg.compute_dtype),
        decode_block=lambda p, h, c, ln, tree=None: model_lib.decode_block_step(
            p, cfg, h, c, ln, kv_chunk=kv_chunk, tree=tree),
        commit=lambda c, kh: model_lib.commit_caches(cfg, c, kh),
        head_logits=lambda p, h, start=0, stop=None: model_lib.all_head_logits(
            p, cfg, h, start, stop),
        p1_logits=lambda p, h: model_lib.base_logits(p, cfg, h),
    )


def seq2seq_backend(cfg: ModelConfig, enc_kvs, enc_mask=None) -> Backend:
    return Backend(
        embed_tokens=lambda p, t: embed_apply(p["embed"], t).astype(cfg.compute_dtype),
        decode_block=lambda p, h, c, ln, tree=None: seq2seq_lib.decode_block_step(
            p, cfg, h, c, ln, enc_kvs, enc_mask, tree=tree),
        commit=lambda c, kh: model_lib.commit_caches(cfg, c, kh),
        head_logits=lambda p, h, start=0, stop=None: seq2seq_lib.all_head_logits(
            p, cfg, h, start, stop),
        p1_logits=lambda p, h: seq2seq_lib.base_logits(p, cfg, h),
    )


# ---------------------------------------------------------------------------
# One BPD iteration (predict+verify merged — paper §4, Fig. 2)
# ---------------------------------------------------------------------------


class BPDState(NamedTuple):
    tokens: jnp.ndarray        # (B, buf) generated+prompt token buffer
    text_len: jnp.ndarray      # (B,) tokens valid in the buffer
    proposals: jnp.ndarray     # (B, k) next block proposals
    caches: Any                # per-layer cache pytree
    finished: jnp.ndarray      # (B,) bool
    iters: jnp.ndarray         # () int32 — model invocations in the loop
    generated: jnp.ndarray     # (B,) int32 — accepted tokens so far
    policy_state: PolicyState = PolicyState()  # loop-carried drafter/schedule


def _freeze_rows(frozen, old_tree, new_tree):
    """Keep the old policy-state rows where ``frozen`` is True.  Policy
    state leaves are batch-leading (B, ...) arrays by contract."""
    def leaf(old, new):
        mask = frozen.reshape((-1,) + (1,) * (new.ndim - 1))
        return jnp.where(mask, old, new)

    return jax.tree_util.tree_map(leaf, old_tree, new_tree)


def bpd_iteration(params, cfg: ModelConfig, dec: DecodeConfig,
                  backend: Backend, state: BPDState, *,
                  prefix_offset: int, max_new, active=None,
                  policy: Optional[DecodePolicy] = None,
                  aux_params=None) -> BPDState:
    """One combined predict/verify/accept step, verify first.

    The trunk runs the k proposals; p_1's logits at those k positions
    verify the block and commit k̂ tokens; then heads p_2..p_block_k run
    on the hidden state at the accepted slot alone (chain: k̂-1; tree: the
    path's node at depth k̂-1), beside p_1's logits already at hand there,
    and the drafter proposes the next block from those (B, block_k, V)
    logits.  The heads' rows at the other k-1 positions are never
    computed: nothing reads them.

    max_new : int or (B,) int32 — per-row generation budget (the serving
              engine gives every slot its own request budget).
    active  : optional (B,) bool — rows with ``active == False`` are slots
              holding no request (continuous batching): they accept nothing,
              write nothing, and keep their state frozen exactly like
              finished rows.
    policy  : decode policy (drafter × acceptor × block schedule); None
              resolves ``dec.policy`` / the legacy ``dec.criterion`` alias.
    aux_params : optional {bundle name: params} of the session's auxiliary
              ``ModelBundle``s, exposed to the drafter via
              ``DraftInputs.aux`` (e.g. the draft model's parameters for
              the ``draft_model`` policy).
    """
    pol = policy_lib.resolve_policy(dec, policy)
    block_k = dec.block_k or cfg.bpd_k
    b = state.proposals.shape[0]
    pos_len = state.text_len + prefix_offset
    topo = pol.drafter.tree_topology(block_k)
    if topo is not None and getattr(pol.schedule, "min_block", 1) > 1:
        raise NotImplementedError(
            "tree verification with min_block > 1 would commit tokens "
            "beyond the accepted root-to-leaf path")

    # ---- parallel scoring of the k proposals (verify ∧ next-predict) ------
    with jax.named_scope("bpd.trunk"):
        h = backend.embed_tokens(params, state.proposals)
        if topo is None:
            hidden, staged = backend.decode_block(params, h, state.caches,
                                                  pos_len)
        else:
            hidden, staged = backend.decode_block(params, h, state.caches,
                                                  pos_len, tree=topo)
    with jax.named_scope("bpd.heads"):
        p1_logits = backend.p1_logits(params, hidden)       # (B, k, V)

    # ---- verify ------------------------------------------------------------
    with jax.named_scope("bpd.verify"):
        if topo is None:
            accepts = pol.acceptor.accepts(state.proposals, p1_logits)
            commit_tokens = state.proposals
            path_nodes = None
        else:
            # Tree verify: node n is checked by p_1 at its PARENT node
            # (each node's logits are ancestor-chain-conditioned thanks to
            # the tree mask).  Permuting the logits by parent turns the tree
            # accept into the ordinary chain accept — including the
            # fused-kernel path; the trailing permutation slot only feeds
            # the always-true column 0.
            perm = tuple(topo.parents[1:]) + (0,)
            acc_nodes = pol.acceptor.accepts(
                state.proposals, p1_logits[:, perm, :])           # (B, N)
            reach = [acc_nodes[:, 0]]              # root: always accepted
            for n in range(1, block_k):
                reach.append(acc_nodes[:, n] & reach[topo.parents[n]])
            reach = jnp.stack(reach, axis=1)                      # (B, N)
            depth = jnp.asarray(topo.depths)
            path_len = jnp.max(jnp.where(reach, depth[None, :] + 1, 0),
                               axis=1)
            # deepest reached node; argmax tie-break = lowest node id
            chosen = jnp.argmax(jnp.where(reach, depth[None, :], -1), axis=1)
            path_nodes = jnp.asarray(topo.path_matrix)[chosen]    # (B, D+1)
            if path_nodes.shape[1] < block_k:
                path_nodes = jnp.pad(
                    path_nodes, ((0, 0), (0, block_k - path_nodes.shape[1])),
                    constant_values=-1)
            commit_tokens = jnp.take_along_axis(
                state.proposals, jnp.clip(path_nodes, 0, block_k - 1), axis=1)
            accepts = (jnp.arange(block_k, dtype=jnp.int32)[None, :]
                       < path_len[:, None])        # chain-shaped for schedule
        remaining = jnp.maximum(max_new - state.generated, 1)
        khat, sched_state = pol.schedule.block_size(
            accepts, remaining, state.policy_state.schedule)  # (B,) in [1, k]
        frozen = (state.finished if active is None
                  else (state.finished | ~active))
        khat = jnp.where(frozen, 0, khat)

        # ---- EOS handling ---------------------------------------------------
        if dec.eos_id >= 0:
            pos_in_block = jnp.arange(block_k, dtype=jnp.int32)[None, :]
            iseos = ((commit_tokens == dec.eos_id)
                     & (pos_in_block < khat[:, None]))
            has_eos = jnp.any(iseos, axis=1)
            first_eos = jnp.argmax(iseos, axis=1)
            khat = jnp.where(has_eos, first_eos + 1, khat)
        else:
            has_eos = jnp.zeros((b,), bool)

    # ---- accept -------------------------------------------------------------
    with jax.named_scope("bpd.commit"):
        offs = jnp.arange(block_k, dtype=jnp.int32)[None, :]
        widx = state.text_len[:, None] + offs
        wmask = offs < khat[:, None]

        def row_write(buf, idx, vals, m):
            old = buf[idx]
            return buf.at[idx].set(jnp.where(m, vals, old))

        tokens = jax.vmap(row_write)(state.tokens, widx, commit_tokens, wmask)
        caches = backend.commit(staged, khat)
        if topo is not None:
            # move the accepted path's KV into chain slots so later
            # iterations see an ordinary committed chain
            caches = model_lib.commit_tree_path(cfg, caches, path_nodes,
                                                khat, pos_len, block_k)
        generated = state.generated + khat
        finished = state.finished | has_eos | (generated >= max_new)

    # ---- heads at the accepted slot (drafted from this same invocation) ----
    with jax.named_scope("bpd.heads"):
        if topo is None:
            slot = jnp.maximum(khat - 1, 0)
        else:
            # the accepted slot is the path's node at depth k̂-1 (root for
            # k̂=0)
            slot = jnp.take_along_axis(
                path_nodes, jnp.maximum(khat - 1, 0)[:, None], axis=1)[:, 0]
            slot = jnp.maximum(slot, 0)
        # p_1 there is already computed: a masked max picks it out reading
        # the (B, k, V) logits once, where the TPU's gather copies them first
        at_slot = jnp.arange(p1_logits.shape[1])[None, :] == slot[:, None]
        logits = jnp.max(jnp.where(at_slot[:, :, None], p1_logits, -jnp.inf),
                         axis=1, keepdims=True)                # (B, 1, V)
        if block_k > 1:
            h_slot = hidden[jnp.arange(b), slot]                   # (B, d)
            logits = jnp.concatenate(
                [logits, backend.head_logits(params, h_slot, 1, block_k)],
                axis=1)                                   # (B, block_k, V)

    with jax.named_scope("bpd.draft"):
        # the committed token at the new text_len - 1 (the last accepted
        # slot; model-backed drafters re-feed it to keep their own cache in
        # sync)
        prev_token = jnp.take_along_axis(
            commit_tokens, jnp.maximum(khat - 1, 0)[:, None], axis=1)[:, 0]
        draft_in = DraftInputs(
            logits=logits, khat=khat, slot=slot,
            text_len=state.text_len + khat, old_proposals=commit_tokens,
            prev_token=prev_token, aux=aux_params or {})
        proposals, draft_state = pol.drafter.draft(
            draft_in, state.policy_state.drafter)
        proposals = jnp.where(frozen[:, None], state.proposals, proposals)
        policy_state = PolicyState(
            drafter=_freeze_rows(frozen, state.policy_state.drafter,
                                 draft_state),
            schedule=_freeze_rows(frozen, state.policy_state.schedule,
                                  sched_state))

    return BPDState(
        tokens=tokens,
        text_len=state.text_len + khat,
        proposals=proposals,
        caches=caches,
        finished=finished,
        iters=state.iters + 1,
        generated=generated,
        policy_state=policy_state,
    )


def initial_draft(pol: DecodePolicy, head_logits: jnp.ndarray,
                  text_len: jnp.ndarray, block_k: int, state, *,
                  prev_token=None, aux_params=None):
    """Draft the FIRST block from a prefill's head logits.

    ``head_logits`` is (B, K, V) at the last context position — the shape
    a loop iteration hands the drafter from its accepted slot (here slot 0,
    k̂ = 1), so one ``draft`` method covers prefill and loop iterations.
    For ``HeadsDrafter`` this reduces exactly to the historical
    ``argmax(head_logits)``; source-drafting policies get to draft from
    their own state immediately instead of spending one iteration on weak
    head proposals.

    ``prev_token`` is the (B,) committed token at ``text_len - 1`` (the
    last prompt token; BOS for seq2seq) and ``aux_params`` the auxiliary
    bundle params — both only consumed by model-backed drafters.
    """
    b = head_logits.shape[0]
    if prev_token is None:
        prev_token = jnp.zeros((b,), jnp.int32)
    din = DraftInputs(
        logits=head_logits[:, :block_k, :],
        khat=jnp.ones((b,), jnp.int32),
        slot=jnp.zeros((b,), jnp.int32),
        text_len=jnp.broadcast_to(jnp.asarray(text_len, jnp.int32), (b,)),
        old_proposals=jnp.zeros((b, block_k), jnp.int32),
        prev_token=jnp.asarray(prev_token, jnp.int32),
        aux=aux_params or {})
    proposals, new_state = pol.drafter.draft(din, state)
    return proposals.astype(jnp.int32), new_state


# ---------------------------------------------------------------------------
# Shared run-to-completion machinery (driven by serving.session.DecodeSession)
# ---------------------------------------------------------------------------


def decode_stats(final) -> Dict:
    """Decode statistics shared by every run-to-completion entry point.

    ``final`` is any loop-final state with ``iters`` / ``generated`` /
    ``text_len`` fields (``BPDState`` or ``GreedyState``).
    ``mean_accepted`` is the paper's headline k̂ metric; ``invocations``
    counts model calls (prefill + loop iterations).
    """
    b = final.generated.shape[0]
    return {
        "iterations": final.iters,
        "generated": final.generated,
        "mean_accepted": jnp.sum(final.generated)
        / jnp.maximum(final.iters, 1) / b,
        "invocations": final.iters + 1,
        "text_len": final.text_len,
    }


def bpd_prefill_causal_lm(params, cfg: ModelConfig, dec: DecodeConfig,
                          batch: Dict, *, max_new: int, kv_chunk: int = 0,
                          policy: Optional[DecodePolicy] = None,
                          aux_params=None):
    """Prefill the caches from the prompt and produce the first proposals."""
    pol = policy_lib.resolve_policy(dec, policy)
    block_k = dec.block_k or cfg.bpd_k
    prompt = batch["tokens"]
    b, prompt_len = prompt.shape
    prefix = model_lib.prefix_len(cfg, batch)
    context_len = prefix + prompt_len + max_new
    # dec.cache_backend selects the KV layout; run-to-completion decode
    # uses the identity-mapped (allocator-free) paged pool
    caches = model_lib.init_caches(cfg, b, context_len, block_k,
                                   backend=cache_lib.get_backend(dec))

    h = model_lib.embed_inputs(params, cfg, batch)          # (B, prefix+P, d)
    positions = jnp.arange(h.shape[1], dtype=jnp.int32)
    hidden, _, caches = model_lib.forward_hidden(
        params, cfg, h, positions=positions, caches=caches, kv_chunk=kv_chunk,
        moe_full_capacity=True)
    last = hidden[:, -1, :]                                 # context = full prompt
    logits = model_lib.all_head_logits(params, cfg, last)   # (B, K, V)
    ps = pol.init_state(cfg, dec, batch, b, aux=aux_params or {})
    proposals, dstate = initial_draft(pol, logits, prompt_len, block_k,
                                      ps.drafter,
                                      prev_token=prompt[:, -1],
                                      aux_params=aux_params)

    buf = prompt_len + max_new + block_k
    tokens = jnp.zeros((b, buf), jnp.int32)
    tokens = tokens.at[:, :prompt_len].set(prompt)
    state = BPDState(
        tokens=tokens,
        text_len=jnp.full((b,), prompt_len, jnp.int32),
        proposals=proposals,
        caches=caches,
        finished=jnp.zeros((b,), bool),
        iters=jnp.zeros((), jnp.int32),
        generated=jnp.zeros((b,), jnp.int32),
        policy_state=ps._replace(drafter=dstate),
    )
    return state, prefix


def _bpd_decode_impl(params, cfg: ModelConfig, dec: DecodeConfig, batch: Dict,
                     row_budget=None, *, backend: Optional[Backend] = None,
                     kv_chunk: int = 0,
                     constrain: Optional[Callable] = None,
                     policy: Optional[DecodePolicy] = None,
                     aux_params=None) -> Tuple[jnp.ndarray, Dict]:
    """Prefill + while_loop for the decoder-only model.

    ``constrain`` (set by a mesh-backed ``DecodeSession``) applies sharding
    constraints to the loop-carried state so GSPMD keeps it partitioned
    through the whole loop.  ``aux_params`` are the auxiliary bundle params
    (loop-invariant, closed over by the body like the primary params).
    """
    max_new = dec.max_new_tokens
    pol = policy_lib.resolve_policy(dec, policy)
    state, prefix = bpd_prefill_causal_lm(params, cfg, dec, batch,
                                          max_new=max_new, kv_chunk=kv_chunk,
                                          policy=pol, aux_params=aux_params)
    if constrain is not None:
        state = constrain(state)
    be = backend or causal_lm_backend(cfg, kv_chunk=kv_chunk)
    budget = max_new if row_budget is None else row_budget

    def cond(s: BPDState):
        return (~jnp.all(s.finished)) & (s.iters < max_new)

    def body(s: BPDState):
        return bpd_iteration(params, cfg, dec, be, s,
                             prefix_offset=prefix, max_new=budget, policy=pol,
                             aux_params=aux_params)

    final = jax.lax.while_loop(cond, body, state)
    return final.tokens, decode_stats(final)


def _session_for(params, cfg, dec, *, mesh=None, session=None, kv_chunk=0,
                 backend=None, policy=None, bundles=None):
    """Resolve the DecodeSession a wrapper should run through.

    When ``session`` is given it takes precedence — its (possibly
    mesh-placed) params are used, so the ``params`` argument is ignored by
    design; cfg/dec/policy however must MATCH the session's, or the caller
    would silently decode under a different geometry/criterion than
    requested.  Otherwise a lightweight local session is built — with
    mesh=None that is trace-transparent and allocation-free.
    """
    if session is not None:
        if session.cfg is not cfg and session.cfg != cfg:
            raise ValueError(
                f"session was built for model config "
                f"{session.cfg.name!r}, called with {cfg.name!r}: build "
                f"one DecodeSession per model")
        if session.dec != dec:
            raise ValueError(
                f"session was built with {session.dec}, called with "
                f"{dec}: a session's decode config is fixed at "
                f"construction — build a new session (or call its "
                f"methods directly)")
        if bundles is not None:
            raise ValueError(
                "bundles are fixed at DecodeSession construction — build "
                "the session with bundles= instead of passing them to the "
                "decode wrapper")
        if policy is not None and \
                policy_lib.resolve_policy(dec, policy).bind(
                    session.bundles, cfg) != session.policy:
            raise ValueError(
                f"session was built with policy "
                f"{session.policy.name!r}, called with {policy!r}: a "
                f"session's decode policy is fixed at construction — "
                f"build a new session")
        return session
    from repro.serving.session import DecodeSession

    return DecodeSession(params, cfg, dec, mesh=mesh, kv_chunk=kv_chunk,
                         backend=backend, policy=policy, bundles=bundles)


def bpd_decode(params, cfg: ModelConfig, dec: DecodeConfig, batch: Dict, *,
               backend: Optional[Backend] = None, kv_chunk: int = 0,
               max_new_rows: Optional[jnp.ndarray] = None,
               mesh=None, session=None, policy=None, bundles=None
               ) -> Tuple[jnp.ndarray, Dict]:
    """Full blockwise parallel decode for the decoder-only model.

    Returns (tokens (B, buf), stats).  stats["mean_accepted"] is the paper's
    headline metric; stats["invocations"] counts model calls (prefill + loop).

    max_new_rows: optional (B,) int32 per-row budgets ≤ dec.max_new_tokens —
    rows stop at their own budget (static-batch serving baseline), while the
    buffers stay sized by dec.max_new_tokens.

    policy: a registered policy name or ``DecodePolicy`` object overriding
    ``dec.policy`` / the legacy ``dec.criterion`` alias for this decode.

    mesh / session: run through a sharding-aware ``DecodeSession`` — params
    placed with ``param_shardings``, the loop jitted with explicit in/out
    shardings.  Default (both None) is the single-device eager path.
    ``mesh=`` is one-shot: it builds (and discards) a fresh session per
    call, re-placing params and recompiling — callers decoding more than
    once should build a ``DecodeSession`` and pass ``session=`` so the
    placement and per-geometry jit cache persist across calls.

    bundles: optional {name: core.bundle.ModelBundle} of auxiliary models
    (e.g. ``{"draft": ModelBundle(draft_params, draft_cfg)}`` for the
    ``draft_model`` policy); fixed at session construction.
    """
    sess = _session_for(params, cfg, dec, mesh=mesh, session=session,
                        kv_chunk=kv_chunk, backend=backend, policy=policy,
                        bundles=bundles)
    return sess.decode(batch, max_new_rows=max_new_rows)


# ---------------------------------------------------------------------------
# Seq2seq decode (the paper's MT experiments): encode once, BPD the decoder.
# ---------------------------------------------------------------------------


def _bpd_decode_seq2seq_impl(params, cfg: ModelConfig, dec: DecodeConfig,
                             batch: Dict,
                             constrain: Optional[Callable] = None,
                             policy: Optional[DecodePolicy] = None,
                             aux_params=None) -> Tuple[jnp.ndarray, Dict]:
    """batch: {"src": (B, Ss)}.  Decoder stream: BOS (token 0) + output."""
    max_new = dec.max_new_tokens
    pol = policy_lib.resolve_policy(dec, policy)
    block_k = dec.block_k or cfg.bpd_k
    src = batch["src"]
    b = src.shape[0]
    enc_kvs, enc_mask = seq2seq_lib.encode(params, cfg, src)
    be = seq2seq_backend(cfg, enc_kvs, enc_mask)

    context_len = 1 + max_new
    caches = seq2seq_lib.init_caches(cfg, b, context_len, block_k)
    bos = jnp.zeros((b, 1), jnp.int32)
    hidden, caches = seq2seq_lib.forward_hidden(params, cfg, bos, enc_kvs,
                                                enc_mask=enc_mask,
                                                caches=caches)
    logits = seq2seq_lib.all_head_logits(params, cfg, hidden[:, -1, :])
    ps = pol.init_state(cfg, dec, batch, b, aux=aux_params or {})
    # the committed token at text_len - 1 is BOS (decoder position 0)
    proposals, dstate = initial_draft(pol, logits, 1, block_k, ps.drafter,
                                      prev_token=bos[:, 0],
                                      aux_params=aux_params)

    buf = 1 + max_new + block_k
    tokens = jnp.zeros((b, buf), jnp.int32)
    state = BPDState(
        tokens=tokens,
        text_len=jnp.ones((b,), jnp.int32),  # BOS occupies position 0
        proposals=proposals,
        caches=caches,
        finished=jnp.zeros((b,), bool),
        iters=jnp.zeros((), jnp.int32),
        generated=jnp.zeros((b,), jnp.int32),
        policy_state=ps._replace(drafter=dstate),
    )
    if constrain is not None:
        state = constrain(state)

    def cond(s: BPDState):
        return (~jnp.all(s.finished)) & (s.iters < max_new)

    def body(s: BPDState):
        return bpd_iteration(params, cfg, dec, be, s, prefix_offset=0,
                             max_new=max_new, policy=pol,
                             aux_params=aux_params)

    final = jax.lax.while_loop(cond, body, state)
    return final.tokens[:, 1:], decode_stats(final)  # strip BOS


def bpd_decode_seq2seq(params, cfg: ModelConfig, dec: DecodeConfig,
                       batch: Dict, *, mesh=None, session=None, policy=None,
                       bundles=None) -> Tuple[jnp.ndarray, Dict]:
    """batch: {"src": (B, Ss)}.  Decoder stream: BOS (token 0) + output.

    ``policy`` / ``bundles`` — see ``bpd_decode``; the seq2seq path
    additionally supports source-drafting policies (``input_copy``), whose
    drafter state is initialized from ``batch["src"]``, and the
    ``draft_model`` policy, whose small causal draft LM runs over the
    decoder token stream.
    """
    sess = _session_for(params, cfg, dec, mesh=mesh, session=session,
                        policy=policy, bundles=bundles)
    return sess.decode_seq2seq(batch)


def greedy_decode_seq2seq(params, cfg: ModelConfig, dec: DecodeConfig,
                          batch: Dict, *, mesh=None, session=None
                          ) -> Tuple[jnp.ndarray, Dict]:
    """Greedy baseline via BPD machinery with block size 1 (p_1 only)."""
    if session is not None:
        if (session.dec.block_k or session.cfg.bpd_k) != 1:
            raise ValueError(
                f"greedy_decode_seq2seq needs a session built with "
                f"block_k=1, got block_k="
                f"{session.dec.block_k or session.cfg.bpd_k}: reusing a "
                f"BPD session would report blockwise iteration stats as "
                f"the greedy baseline")
        return session.decode_seq2seq(batch)
    return bpd_decode_seq2seq(params, cfg, dec.replace(block_k=1), batch,
                              mesh=mesh)


# ---------------------------------------------------------------------------
# Greedy baseline (paper §2) — identical machinery with block size 1,
# scoring only p_1 (no head overhead), for fair wall-clock comparisons.
# ---------------------------------------------------------------------------


class GreedyState(NamedTuple):
    tokens: jnp.ndarray        # (B, buf) prompt+output token buffer
    text_len: jnp.ndarray      # (B,) tokens valid in the buffer
    tok: jnp.ndarray           # (B,) next token to commit
    caches: Any                # per-layer cache pytree
    finished: jnp.ndarray      # (B,) bool
    iters: jnp.ndarray         # () int32 — decode steps taken
    generated: jnp.ndarray     # (B,) int32 — committed tokens so far


def _greedy_decode_impl(params, cfg: ModelConfig, dec: DecodeConfig,
                        batch: Dict, *, kv_chunk: int = 0,
                        constrain: Optional[Callable] = None
                        ) -> Tuple[jnp.ndarray, Dict]:
    max_new = dec.max_new_tokens
    prompt = batch["tokens"]
    b, prompt_len = prompt.shape
    prefix = model_lib.prefix_len(cfg, batch)
    context_len = prefix + prompt_len + max_new
    caches = model_lib.init_caches(cfg, b, context_len, 1,
                                   backend=cache_lib.get_backend(dec))

    h = model_lib.embed_inputs(params, cfg, batch)
    positions = jnp.arange(h.shape[1], dtype=jnp.int32)
    hidden, _, caches = model_lib.forward_hidden(
        params, cfg, h, positions=positions, caches=caches, kv_chunk=kv_chunk,
        moe_full_capacity=True)
    logits = model_lib.base_logits(params, cfg, hidden[:, -1, :])
    next_tok = jnp.argmax(logits, axis=-1)                   # (B,)

    buf = prompt_len + max_new + 1
    tokens = jnp.zeros((b, buf), jnp.int32).at[:, :prompt_len].set(prompt)
    state = GreedyState(
        tokens=tokens,
        text_len=jnp.full((b,), prompt_len, jnp.int32),
        tok=next_tok.astype(jnp.int32),
        caches=caches,
        finished=jnp.zeros((b,), bool),
        iters=jnp.zeros((), jnp.int32),
        generated=jnp.zeros((b,), jnp.int32),
    )
    if constrain is not None:
        state = constrain(state)

    def cond(s: GreedyState):
        return (~jnp.all(s.finished)) & (s.iters < max_new)

    def body(s: GreedyState):
        adv = (~s.finished).astype(jnp.int32)
        tokens = jax.vmap(lambda bu, i, v, m: bu.at[i].set(
            jnp.where(m, v, bu[i])))(s.tokens, s.text_len, s.tok, ~s.finished)
        h = embed_apply(params["embed"], s.tok[:, None]).astype(cfg.compute_dtype)
        hidden, staged = model_lib.decode_block_step(
            params, cfg, h, s.caches, s.text_len + prefix, kv_chunk=kv_chunk)
        caches = model_lib.commit_caches(cfg, staged, adv)
        logits = model_lib.base_logits(params, cfg, hidden[:, 0, :])
        new_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        text_len = s.text_len + adv
        finished = s.finished
        if dec.eos_id >= 0:
            finished = finished | (s.tok == dec.eos_id)
        finished = finished | (text_len - prompt_len >= max_new)
        tok = jnp.where(finished, s.tok, new_tok)
        return GreedyState(tokens=tokens, text_len=text_len, tok=tok,
                           caches=caches, finished=finished,
                           iters=s.iters + 1, generated=s.generated + adv)

    final = jax.lax.while_loop(cond, body, state)
    return final.tokens, decode_stats(final)


def greedy_decode(params, cfg: ModelConfig, dec: DecodeConfig, batch: Dict, *,
                  kv_chunk: int = 0, mesh=None, session=None
                  ) -> Tuple[jnp.ndarray, Dict]:
    sess = _session_for(params, cfg, dec, mesh=mesh, session=session,
                        kv_chunk=kv_chunk)
    return sess.greedy(batch)
