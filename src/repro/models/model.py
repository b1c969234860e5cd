"""Model assembly: decoder-only ``CausalLM`` (all assigned text archs, the
VLM backbone, and the RWKV/Hymba families) and the encoder-only stack
(hubert).  The encoder-decoder MT model from the paper lives in seq2seq.py.

All functions are pure; parameters/caches are dict pytrees.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.core.heads import heads_apply, heads_init
from repro.models import cache as cache_lib
from repro.models.blocks import (
    block_cached,
    block_cache_init,
    block_full,
    block_init,
    commit_cache,
)
from repro.models.layers import (
    dense_apply,
    dense_init,
    embed_apply,
    embed_init,
    norm_apply,
    norm_init,
    unembed_apply,
)
from repro.sharding.policy import param_shardings


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init(key, cfg: ModelConfig) -> Dict:
    dtype = cfg.params_dtype
    ks = jax.random.split(key, cfg.num_layers + 5)
    p: Dict = {
        "embed": embed_init(ks[0], cfg.padded_vocab_size, cfg.d_model,
                            dtype=dtype),
        "blocks": [block_init(ks[1 + i], cfg, i, dtype=dtype)
                   for i in range(cfg.num_layers)],
        "final_norm": norm_init(cfg.d_model, kind=cfg.norm_type, dtype=dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(ks[cfg.num_layers + 1], cfg.d_model,
                                  cfg.padded_vocab_size, dtype=dtype)
    if cfg.bpd_enabled:
        p["bpd_heads"] = heads_init(ks[cfg.num_layers + 2], cfg, dtype=dtype)
    if cfg.num_meta_tokens:
        p["meta_tokens"] = jax.random.normal(
            ks[cfg.num_layers + 3], (cfg.num_meta_tokens, cfg.d_model),
            dtype) * 0.02
    if cfg.is_encoder_only:
        p["pos_embed"] = jax.random.normal(
            ks[cfg.num_layers + 4], (cfg.max_seq_len, cfg.d_model), dtype) * 0.02
        p["mask_embed"] = jax.random.normal(
            jax.random.fold_in(key, 99), (cfg.d_model,), dtype) * 0.02
    return p


def init_params(key, cfg: ModelConfig, mesh=None) -> Dict:
    """``init`` as one jitted program: parameters are drawn on the device
    in ``cfg.param_dtype`` and, given ``mesh``, straight into
    ``sharding.policy.param_shardings`` — never assembled whole on one
    device first."""
    def init_params_program(k):
        return init(k, cfg)

    if mesh is None:
        return jax.jit(init_params_program)(key)
    shardings = param_shardings(jax.eval_shape(init_params_program, key),
                                mesh)
    return jax.jit(init_params_program, out_shardings=shardings)(key)


# ---------------------------------------------------------------------------
# Input embedding (text / vision_text / audio)
# ---------------------------------------------------------------------------


def embed_inputs(params, cfg: ModelConfig, batch: Dict) -> jnp.ndarray:
    """batch keys by modality:
       text        : tokens (B, S) int32
       vision_text : patch_embeds (B, P, d) float + tokens (B, S-P-meta)
       audio       : frame_embeds (B, S, d) float [+ mask (B, S) bool]
    Meta tokens (hymba) are prepended here.
    """
    dtype = cfg.compute_dtype
    if cfg.modality == "audio":
        h = batch["frame_embeds"].astype(dtype)
        if "mask" in batch:  # masked-prediction corruption (hubert training)
            m = batch["mask"][..., None]
            h = jnp.where(m, params["mask_embed"].astype(dtype), h)
        s = h.shape[1]
        h = h + params["pos_embed"][:s].astype(dtype)
        return h
    parts = []
    if cfg.num_meta_tokens:
        b = (batch["tokens"] if "tokens" in batch else batch["patch_embeds"]).shape[0]
        meta = jnp.broadcast_to(params["meta_tokens"].astype(dtype),
                                (b, cfg.num_meta_tokens, cfg.d_model))
        parts.append(meta)
    if cfg.modality == "vision_text" and "patch_embeds" in batch:
        parts.append(batch["patch_embeds"].astype(dtype))
    parts.append(embed_apply(params["embed"], batch["tokens"]).astype(dtype))
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]


def prefix_len(cfg: ModelConfig, batch: Dict) -> int:
    """Number of non-text positions preceding the text tokens."""
    n = cfg.num_meta_tokens
    if cfg.modality == "vision_text" and "patch_embeds" in batch:
        n += batch["patch_embeds"].shape[1]
    return n


# ---------------------------------------------------------------------------
# Backbone forwards
# ---------------------------------------------------------------------------


def forward_hidden(params, cfg: ModelConfig, h, *, positions=None,
                   bidirectional: bool = False, caches=None, kv_chunk: int = 0,
                   moe_full_capacity: bool = False):
    """Whole-sequence forward. h: (B,S,d) embeddings.

    Returns (hidden, metrics, caches) — caches populated if given (prefill).
    """
    metrics: Dict = {}
    new_caches = list(caches) if caches is not None else None
    use_remat = cfg.remat and caches is None   # training forward only

    def run_block(i, bp, h, c):
        return block_full(bp, cfg, i, h, positions=positions,
                          bidirectional=bidirectional, cache=c,
                          kv_chunk=kv_chunk,
                          moe_full_capacity=moe_full_capacity)

    for i, bp in enumerate(params["blocks"]):
        c = caches[i] if caches is not None else None
        if use_remat:
            h, m, c_out = jax.checkpoint(
                lambda bp_, h_, i_=i: run_block(i_, bp_, h_, None))(bp, h)
        else:
            h, m, c_out = run_block(i, bp, h, c)
        for k, v in m.items():
            metrics[k] = metrics.get(k, 0.0) + v / cfg.num_layers
        if caches is not None:
            new_caches[i] = c_out
    h = norm_apply(params["final_norm"], h, kind=cfg.norm_type)
    return h, metrics, (tuple(new_caches) if new_caches is not None else None)


def decode_block_step(params, cfg: ModelConfig, h, caches, length, *,
                      kv_chunk: int = 0, tree=None):
    """BPD verify-substep backbone: k fresh embeddings vs the caches.

    Returns (hidden_block, staged_caches). staged caches carry stacked
    per-step recurrent states; call ``commit_caches`` with k̂ to resolve.
    ``tree`` switches the block to tree verification (see
    ``models.attention.attn_cached``).
    """
    new_caches = []
    for i, bp in enumerate(params["blocks"]):
        h, c_out = block_cached(bp, cfg, i, h, caches[i], length,
                                kv_chunk=kv_chunk, tree=tree)
        new_caches.append(c_out)
    h = norm_apply(params["final_norm"], h, kind=cfg.norm_type)
    return h, tuple(new_caches)


def commit_caches(cfg: ModelConfig, caches, khat):
    return tuple(commit_cache(cfg, c, khat) for c in caches)


def commit_tree_path(cfg: ModelConfig, caches, path_nodes, khat, length,
                     block_k: int):
    """Compact the accepted root-to-leaf path into chain slots per layer
    after a tree verify forward (see ``attention.tree_commit_attn``)."""
    from repro.models.attention import tree_commit_attn

    out = []
    for i, c in enumerate(caches):
        nc = dict(c)
        if "attn" in c:
            nc["attn"] = tree_commit_attn(c["attn"], cfg, i, path_nodes,
                                          khat, length, block_k)
        out.append(nc)
    return tuple(out)


def init_caches(cfg: ModelConfig, batch: int, context_len: int, block_k: int,
                dtype=None, *, backend=None):
    """``backend`` (a ``cache.KVCacheBackend``) selects the attention cache
    layout — dense slabs (default) or the paged pool; recurrent caches are
    layout-independent."""
    dtype = dtype or cfg.compute_dtype
    return tuple(block_cache_init(cfg, i, batch, context_len, block_k, dtype,
                                  backend=backend)
                 for i in range(cfg.num_layers))


def reset_cache_rows(caches, mask):
    """Invalidate rows ``mask`` ((B,) bool) across every layer's cache —
    slot eviction for the continuous-batching serving engine."""
    return tuple(cache_lib.reset_rows(c, mask) for c in caches)


def scatter_cache_row(caches, row_caches, slot, *, constraint=None,
                      tbl_row=None, write_mask=None):
    """Insert a batch-1 cache pytree into row ``slot`` of a batched cache —
    prefill-into-freed-slot for the continuous-batching serving engine.
    ``constraint`` optionally pins per-layer shardings (see cache.scatter_row)
    so admission stays a shard-local write on a mesh.  For paged layers
    ``tbl_row`` / ``write_mask`` carry the host allocator's page mapping
    (one mapping serves every layer — see cache.scatter_row_paged)."""
    if constraint is None:
        constraint = (None,) * len(caches)
    out = []
    for c, rc, cn in zip(caches, row_caches, constraint):
        if cache_lib.is_paged(c):
            out.append(cache_lib.scatter_row_paged(
                c, rc, slot, tbl_row, write_mask, constraint=cn))
        else:
            out.append(cache_lib.scatter_row(c, rc, slot, constraint=cn))
    return tuple(out)


# ---------------------------------------------------------------------------
# Output projections
# ---------------------------------------------------------------------------


def project_vocab(params, cfg: ModelConfig, h) -> jnp.ndarray:
    """(..., d) -> (..., padded_vocab) logits; pad lanes masked to -inf so
    argmax / softmax never select them (see ModelConfig.padded_vocab_size)."""
    if cfg.tie_embeddings:
        logits = unembed_apply(params["embed"], h)
    else:
        logits = dense_apply(params["lm_head"], h)
    if cfg.padded_vocab_size != cfg.vocab_size:
        lane = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                        logits.ndim - 1)
        logits = jnp.where(lane < cfg.vocab_size, logits,
                           jnp.asarray(-1e9, logits.dtype))
    return logits


def _has_heads(params, cfg: ModelConfig) -> bool:
    return cfg.bpd_enabled and "bpd_heads" in params


def all_head_logits(params, cfg: ModelConfig, hidden, start: int = 0,
                    stop=None) -> jnp.ndarray:
    """hidden: (..., d) -> (..., n, V) logits of heads p_{start+1} ..
    p_stop (static; p_1..p_k by default, paper Fig. 3).  A headless model
    has p_1 only (greedy-decodable via block_k=1), so ``start`` ≥ 1 gives
    no rows there."""
    if not _has_heads(params, cfg):
        outs = hidden[..., None, :][..., start:stop, :]
    else:
        outs = heads_apply(params["bpd_heads"], cfg, hidden,
                           identity_p1=cfg.bpd_identity_p1,
                           start=start, stop=stop)
    return project_vocab(params, cfg, outs)


def base_logits(params, cfg: ModelConfig, hidden) -> jnp.ndarray:
    """hidden: (..., d) -> (..., V) p_1 logits only."""
    if _has_heads(params, cfg) and not cfg.bpd_identity_p1:
        from repro.core.heads import head_apply_single
        hidden = head_apply_single(params["bpd_heads"], cfg, hidden, 0,
                                   identity_p1=False)
    return project_vocab(params, cfg, hidden)
