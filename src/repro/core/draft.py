"""Draft-model speculative drafting: a small causal LM proposes the block.

The paper predicts the k block tokens with prediction heads bolted onto the
verifier; the stronger form in the BPD-drafts follow-up (arXiv:2404.09221)
and Aggressive Decoding (arXiv:2205.10350) replaces the heads with an
*independent small draft model* that proposes the block autoregressively —
cheap, because it is tiny — while the big model verifies the whole block in
one invocation.  Exact acceptance keeps this lossless: slot 0 of every
draft is pinned to the verifier's own greedy token, so the decoded tokens
equal greedy decoding for ANY draft model; draft quality moves iteration
counts only.

``DraftModelDrafter`` is a ``core.policy.Drafter`` backed by an auxiliary
``core.bundle.ModelBundle`` (bound at session construction via
``DecodePolicy.bind``).  Its loop-carried state is the draft model's own
KV cache, living inside ``BPDState.policy_state`` / ``SlotBatch.
policy_state`` like any other per-row policy state: it shards over the
data axes (``sharding.policy.state_specs`` applies the draft model's own
``cache_specs`` when given ``draft_cfg`` — the session reads it off the
bound drafter), freezes with finished rows, and is reset/scattered by
the serving engine on admit/evict.

Cache discipline (why one catch-up token is always enough): the draft
chain written at iteration t covers positions L..L+k-2 (slot 0 = the
verified token at L, then the chain), and the verifier commits exactly
that chain prefix — so after accepting k̂ tokens the draft cache already
holds the committed stream except, when k̂ = k, the single position
L+k-1.  Each draft therefore re-feeds ``prev_token`` (the committed token
at ``text_len - 1``) before extending; attention-cache staleness beyond
``text_len`` is handled by the same absolute-position masking that powers
BPD rollback (models/cache.py).  That argument is KV-only, hence the
attention-family restriction on the draft config.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax.numpy as jnp

from repro.config import ModelConfig
from repro.core import policy as policy_lib

I32 = jnp.int32

DRAFT_BUNDLE = "draft"  # the session bundle name this drafter reads


@dataclasses.dataclass(frozen=True)
class DraftModelDrafter(policy_lib.Drafter):
    """Propose ``block_k`` tokens with a small causal draft LM.

    Unbound (``cfg is None``) until ``DecodePolicy.bind`` attaches the
    session's ``bundles["draft"]``; the params themselves arrive traced,
    per call, via ``DraftInputs.aux["draft"]``.
    """

    cfg: Optional[ModelConfig] = None      # the DRAFT model's config
    kv_chunk: int = 0
    backend_factory: Optional[Callable] = None
    bundle: str = DRAFT_BUNDLE
    # Suffix carry-over: fold the catch-up token into the first extension
    # as one width-2 draft forward, cutting the sequential draft-model
    # calls per iteration from block_k to block_k - 1 (token-identical —
    # the position text_len-1 rewrite is value-identical and absolute-
    # position masking hides the stale text_len entry from it).
    carry_over: bool = True

    # -- binding --------------------------------------------------------------

    def bind(self, bundles: Dict, cfg) -> "DraftModelDrafter":
        b = (bundles or {}).get(self.bundle)
        if b is None:
            raise ValueError(
                f"the 'draft_model' policy runs a second model: pass "
                f"bundles={{{self.bundle!r}: ModelBundle(draft_params, "
                f"draft_cfg)}} to the DecodeSession / decode entry point "
                f"(got bundles={sorted(bundles or {})})")
        d = b.cfg
        if d.is_mla or (cfg is not None and cfg.is_mla):
            raise NotImplementedError(
                "the 'draft_model' policy is not implemented for latent "
                "attention (MLA) models, as target or as draft")
        if d.block_type != "attn":
            raise NotImplementedError(
                f"draft model {d.name!r} has block_type={d.block_type!r}: "
                f"the draft cache rolls back rejected speculation by "
                f"absolute-position masking, which only KV caches support "
                f"— recurrent draft states would keep rejected tokens")
        if d.is_encoder_decoder or d.is_encoder_only:
            raise ValueError(
                f"draft model {d.name!r} must be decoder-only: it drafts "
                f"the output token stream autoregressively")
        if d.num_meta_tokens or d.modality != "text":
            raise NotImplementedError(
                f"draft model {d.name!r} must be a plain text LM (no meta "
                f"tokens / modality prefixes): draft positions are output-"
                f"stream positions")
        if cfg is not None and d.vocab_size != cfg.vocab_size:
            raise ValueError(
                f"draft model vocab_size={d.vocab_size} != primary model "
                f"vocab_size={cfg.vocab_size}: proposals are token ids in "
                f"the primary vocabulary")
        return dataclasses.replace(self, cfg=d, kv_chunk=b.kv_chunk,
                                   backend_factory=b.backend_factory)

    def _require_bound(self):
        if self.cfg is None:
            raise ValueError(
                "DraftModelDrafter is unbound — resolve the 'draft_model' "
                "policy through a DecodeSession (or call DecodePolicy.bind) "
                "with a 'draft' ModelBundle before decoding")

    def _backend(self):
        from repro.core.decode import causal_lm_backend

        if self.backend_factory is not None:
            return self.backend_factory(self.cfg, self.kv_chunk)
        return causal_lm_backend(self.cfg, kv_chunk=self.kv_chunk)

    # -- state ----------------------------------------------------------------

    def init_state(self, cfg, dec, batch, b, aux=()) -> Any:
        """Draft KV cache for ``b`` rows, prefilled on the prompt when the
        caller can supply both the prompt tokens and the draft params.

        Shape contract: the cache geometry depends only on (prompt length,
        dec, block_k), never on whether ``aux`` was available — so the
        engine's paramless init/evict builders produce states congruent
        with the admission path's prefilled rows.
        """
        self._require_bound()
        from repro.models import model as model_lib

        block_k = dec.block_k or cfg.bpd_k
        tokens = batch.get("tokens") if isinstance(batch, dict) else None
        # seq2seq / promptless paths: the draft stream starts at BOS (pos 0)
        prompt_len = 1 if tokens is None else tokens.shape[1]
        context = prompt_len + dec.max_new_tokens + block_k
        caches = model_lib.init_caches(self.cfg, b, context, 1)
        params = aux[self.bundle] if aux and self.bundle in aux else None
        if params is not None and tokens is not None:
            from repro.models.layers import embed_apply

            h = embed_apply(params["embed"], jnp.asarray(tokens, I32))
            h = h.astype(self.cfg.compute_dtype)
            positions = jnp.arange(h.shape[1], dtype=I32)
            _, _, caches = model_lib.forward_hidden(
                params, self.cfg, h, positions=positions, caches=caches,
                kv_chunk=self.kv_chunk, moe_full_capacity=True)
        return {"caches": caches}

    # -- drafting -------------------------------------------------------------

    def draft(self, inputs: policy_lib.DraftInputs, state: Any):
        self._require_bound()
        if not (inputs.aux and self.bundle in inputs.aux):
            raise ValueError(
                f"DraftModelDrafter needs its params in DraftInputs.aux"
                f"[{self.bundle!r}] — this decode path was not built with "
                f"the session's auxiliary bundles threaded through")
        params = inputs.aux[self.bundle]
        be = self._backend()
        b, k = inputs.old_proposals.shape
        ones = jnp.ones((b,), I32)
        caches = state["caches"]

        def step(tok, caches, pos):
            """One draft-model token: feed ``tok`` at per-row ``pos``."""
            h = be.embed_tokens(params, tok[:, None])
            hidden, staged = be.decode_block(params, h, caches, pos)
            caches = be.commit(staged, ones)
            logits = be.head_logits(params, hidden)    # (B, 1, K', V)
            return jnp.argmax(logits[:, 0, 0, :], axis=-1).astype(I32), caches

        verified = jnp.argmax(inputs.logits[:, 0], axis=-1).astype(I32)
        prev = jnp.asarray(inputs.prev_token, I32)
        pos0 = jnp.maximum(inputs.text_len - 1, 0)

        props = [verified]
        if self.carry_over and k > 1:
            # carry-over: the catch-up token (committed at text_len - 1)
            # and the verified slot-0 token ride one width-2 forward at
            # positions [text_len-1, text_len] — the rewrite at text_len-1
            # is value-identical, and the query there cannot see the stale
            # speculative entry at text_len (absolute-position masking),
            # while the verified-token query reads the fresh write.  One
            # sequential draft call replaces two.
            h = be.embed_tokens(params, jnp.stack([prev, verified], axis=1))
            hidden, staged = be.decode_block(params, h, caches, pos0)
            caches = be.commit(staged, ones)
            logits = be.head_logits(params, hidden)    # (B, 2, K', V)
            tok = jnp.argmax(logits[:, 1, 0, :], axis=-1).astype(I32)
            props.append(tok)
            start = 2
        else:
            # catch-up: re-feed the committed token at text_len - 1 so the
            # cache covers the full verified stream (see module docstring);
            # its prediction is discarded — slot 0 is the verifier's token
            _, caches = step(prev, caches, pos0)
            tok = verified
            start = 1
        for i in range(start, k):
            tok, caches = step(tok, caches, inputs.text_len - 1 + i)
            props.append(tok)
        return jnp.stack(props, axis=1), {"caches": caches}

    def draft_steps_per_iter(self, block_k: int) -> int:
        """Sequential draft-model forwards issued per BPD iteration."""
        if self.carry_over and block_k > 1:
            return block_k - 1
        return block_k


policy_lib.register_policy("draft_model", lambda dec: policy_lib.DecodePolicy(
    DraftModelDrafter(),
    policy_lib._maybe_fused(policy_lib.ExactAcceptor(), dec),
    policy_lib._schedule_for(dec), name="draft_model"))
