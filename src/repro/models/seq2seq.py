"""Encoder-decoder transformer — the paper's machine-translation setting.

Encoder: bidirectional attention blocks (learned positional embeddings).
Decoder: causal blocks with cross attention; BPD heads sit on the decoder
output exactly as in the decoder-only case.  The cross-attention K/V are
computed once per source sentence ("encode") and threaded through decode.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.core.heads import heads_init
from repro.models.attention import cross_kv
from repro.models.blocks import block_cached, block_cache_init, block_full, block_init
from repro.models.layers import (
    dense_apply,
    dense_init,
    embed_apply,
    embed_init,
    norm_apply,
    norm_init,
    unembed_apply,
)
from repro.models import model as model_lib


def init(key, cfg: ModelConfig) -> Dict:
    dtype = cfg.params_dtype
    ne, nd = cfg.num_encoder_layers, cfg.num_layers
    ks = jax.random.split(key, ne + nd + 6)
    p: Dict = {
        "src_embed": embed_init(ks[0], cfg.padded_vocab_size, cfg.d_model,
                                dtype=dtype),
        "embed": embed_init(ks[1], cfg.padded_vocab_size, cfg.d_model,
                            dtype=dtype),
        "enc_pos": jax.random.normal(ks[2], (cfg.max_seq_len, cfg.d_model),
                                     dtype) * 0.02,
        "enc_blocks": [block_init(ks[3 + i], cfg, i, dtype=dtype)
                       for i in range(ne)],
        "enc_norm": norm_init(cfg.d_model, kind=cfg.norm_type, dtype=dtype),
        "blocks": [block_init(ks[3 + ne + i], cfg, i, dtype=dtype,
                              cross_attention=True) for i in range(nd)],
        "final_norm": norm_init(cfg.d_model, kind=cfg.norm_type, dtype=dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(ks[3 + ne + nd], cfg.d_model,
                                  cfg.padded_vocab_size, dtype=dtype)
    if cfg.bpd_enabled:
        p["bpd_heads"] = heads_init(ks[4 + ne + nd], cfg, dtype=dtype)
    return p


def encode(params, cfg: ModelConfig, src_tokens, src_mask=None):
    """src_tokens: (B, Se) -> per-decoder-layer cross K/V + mask."""
    dtype = cfg.compute_dtype
    h = embed_apply(params["src_embed"], src_tokens).astype(dtype)
    h = h + params["enc_pos"][: h.shape[1]].astype(dtype)
    for i, bp in enumerate(params["enc_blocks"]):
        h, _, _ = block_full(bp, cfg, i, h, bidirectional=True)
    h = norm_apply(params["enc_norm"], h, kind=cfg.norm_type)
    enc_kvs = tuple(cross_kv(bp["cross"], cfg, h) for bp in params["blocks"])
    return enc_kvs, src_mask


def forward_hidden(params, cfg: ModelConfig, tgt_tokens, enc_kvs, *,
                   enc_mask=None, caches=None):
    """Teacher-forced decoder forward (training / prefill)."""
    dtype = cfg.compute_dtype
    h = embed_apply(params["embed"], tgt_tokens).astype(dtype)
    new_caches = list(caches) if caches is not None else None
    for i, bp in enumerate(params["blocks"]):
        c = caches[i] if caches is not None else None
        h, _, c_out = block_full(bp, cfg, i, h, enc_kv=enc_kvs[i],
                                 enc_mask=enc_mask, cache=c)
        if caches is not None:
            new_caches[i] = c_out
    h = norm_apply(params["final_norm"], h, kind=cfg.norm_type)
    return h, (tuple(new_caches) if new_caches is not None else None)


def decode_block_step(params, cfg: ModelConfig, h, caches, length, enc_kvs,
                      enc_mask=None, tree=None):
    new_caches = []
    for i, bp in enumerate(params["blocks"]):
        h, c_out = block_cached(bp, cfg, i, h, caches[i], length,
                                enc_kv=enc_kvs[i], enc_mask=enc_mask,
                                tree=tree)
        new_caches.append(c_out)
    h = norm_apply(params["final_norm"], h, kind=cfg.norm_type)
    return h, tuple(new_caches)


def init_caches(cfg: ModelConfig, batch: int, context_len: int, block_k: int,
                dtype=None):
    dtype = dtype or cfg.compute_dtype
    return tuple(block_cache_init(cfg, i, batch, context_len, block_k, dtype)
                 for i in range(cfg.num_layers))


# Output projections are identical to the decoder-only model (lazy
# delegation: model_lib may still be mid-import when this module loads).


def project_vocab(params, cfg, h):
    return model_lib.project_vocab(params, cfg, h)


def all_head_logits(params, cfg, hidden, start=0, stop=None):
    return model_lib.all_head_logits(params, cfg, hidden, start, stop)


def base_logits(params, cfg, hidden):
    return model_lib.base_logits(params, cfg, hidden)
