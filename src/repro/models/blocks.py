"""Per-layer block composition.

One ``Block`` covers every assigned family via ``cfg.block_type`` ×
``cfg.mlp_type``:

  attn  + dense     : stablelm / starcoder2 / nemotron / granite / llava /
                      hubert (bidirectional) / the paper's MT transformer
  attn  + moe       : qwen2-moe, olmoe, deepseek-v2-lite (latent attention,
                      dense layers below ``first_k_dense_replace``)
  rwkv6 + channel   : rwkv6 ("Finch")
  hymba + dense     : hymba (parallel attention + mamba heads, fused)

Two execution modes:
  * full   — whole-sequence parallel forward (training / prefill / encoder);
             optionally populates the decode caches.
  * cached — a block of ``k`` fresh tokens against the caches (the BPD
             verify substep).  Recurrent components return *per-step* states
             stacked along a leading axis so the decode loop can roll back to
             the accepted prefix; ``commit_cache`` selects the accepted step.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import cache as cache_lib
from repro.models.attention import (
    attn_cached,
    attn_full,
    attn_init,
    cache_write,
    cross_attn_apply,
    cross_attn_init,
    cross_kv,
)
from repro.models.layers import mlp_apply, mlp_init, norm_apply, norm_init
from repro.models.mamba import mamba_apply, mamba_init
from repro.models.moe import moe_apply, moe_init
from repro.models.rwkv6 import (
    rwkv_cm_apply,
    rwkv_cm_init,
    rwkv_tm_apply,
    rwkv_tm_init,
)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def block_init(key, cfg: ModelConfig, layer_idx: int, *, dtype=jnp.float32,
               cross_attention: bool = False) -> Dict:
    ks = jax.random.split(key, 8)
    p: Dict = {"ln1": norm_init(cfg.d_model, kind=cfg.norm_type, dtype=dtype)}

    if cfg.block_type == "attn":
        p["attn"] = attn_init(ks[0], cfg, dtype=dtype)
    elif cfg.block_type == "rwkv6":
        p["tm"] = rwkv_tm_init(ks[0], cfg, dtype=dtype)
    elif cfg.block_type == "hymba":
        p["attn"] = attn_init(ks[0], cfg, dtype=dtype)
        p["mamba"] = mamba_init(ks[1], cfg, dtype=dtype)
        p["fuse_ln_attn"] = norm_init(cfg.d_model, kind="rmsnorm", dtype=dtype)
        p["fuse_ln_ssm"] = norm_init(cfg.d_model, kind="rmsnorm", dtype=dtype)
        p["beta_attn"] = jnp.ones((cfg.d_model,), dtype)
        p["beta_ssm"] = jnp.ones((cfg.d_model,), dtype)
    else:
        raise ValueError(cfg.block_type)

    if cross_attention:
        p["ln_cross"] = norm_init(cfg.d_model, kind=cfg.norm_type, dtype=dtype)
        p["cross"] = cross_attn_init(ks[2], cfg, dtype=dtype)

    p["ln2"] = norm_init(cfg.d_model, kind=cfg.norm_type, dtype=dtype)
    mlp_type = cfg.layer_mlp_type(layer_idx)
    if mlp_type == "dense":
        p["mlp"] = mlp_init(ks[3], cfg, d_ff=cfg.dense_d_ff or None,
                            dtype=dtype)
    elif mlp_type == "moe":
        p["moe"] = moe_init(ks[3], cfg, dtype=dtype)
    elif mlp_type == "rwkv_channel_mix":
        p["cm"] = rwkv_cm_init(ks[3], cfg, dtype=dtype)
    else:
        raise ValueError(cfg.mlp_type)
    return p


def block_cache_init(cfg: ModelConfig, layer_idx: int, batch: int,
                     context_len: int, block_k: int, dtype,
                     backend: Optional[cache_lib.KVCacheBackend] = None) -> Dict:
    """Static cache buffers for one layer (decode path).  ``backend``
    (a ``cache.KVCacheBackend``) owns the attention-cache layout; None
    means the dense default."""
    c: Dict = {}
    if cfg.block_type in ("attn", "hymba"):
        be = backend if backend is not None else cache_lib.DenseBackend()
        c["attn"] = be.layer_attn_init(cfg, layer_idx, batch, context_len,
                                       block_k, dtype)
    if cfg.block_type == "rwkv6":
        h = cfg.d_model // cfg.rwkv_head_dim
        c["tm"] = cache_lib.rwkv_cache_init(batch, cfg.d_model, h,
                                            cfg.rwkv_head_dim, dtype)
    if cfg.block_type == "hymba":
        c["mamba"] = cache_lib.mamba_cache_init(
            batch, cfg.ssm_expand * cfg.d_model, cfg.ssm_state_dim,
            cfg.ssm_conv_width, dtype)
    return c


# ---------------------------------------------------------------------------
# Full-sequence forward (training / prefill / encoder)
# ---------------------------------------------------------------------------


def block_full(p, cfg: ModelConfig, layer_idx: int, x, *, positions=None,
               bidirectional: bool = False, enc_kv=None, enc_mask=None,
               cache: Optional[Dict] = None, kv_chunk: int = 0,
               moe_full_capacity: bool = False
               ) -> Tuple[jnp.ndarray, Dict, Optional[Dict]]:
    """Returns (y, metrics, cache_out). cache_out is populated when a cache
    dict is passed in (prefill)."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)
    metrics: Dict = {}
    cache_out = dict(cache) if cache is not None else None

    h = norm_apply(p["ln1"], x, kind=cfg.norm_type)
    if cfg.block_type == "attn":
        if cache is not None:
            y, (kk, vv) = attn_full(p["attn"], cfg, h, layer_idx=layer_idx,
                                    positions=positions,
                                    bidirectional=bidirectional,
                                    return_kv=True, kv_chunk=kv_chunk)
            cache_out["attn"] = cache_write(cache["attn"], cfg, layer_idx,
                                            kk, vv, positions)
        else:
            y = attn_full(p["attn"], cfg, h, layer_idx=layer_idx,
                          positions=positions, bidirectional=bidirectional,
                          kv_chunk=kv_chunk)
    elif cfg.block_type == "rwkv6":
        y, aux = rwkv_tm_apply(p["tm"], cfg, h)
        if cache is not None:
            cache_out["tm"] = {
                "shift_tm": aux["x_last"],
                "shift_cm": cache["tm"]["shift_cm"],  # filled below
                "state": aux["state"],
            }
    elif cfg.block_type == "hymba":
        ya, (kk, vv) = attn_full(p["attn"], cfg, h, layer_idx=layer_idx,
                                 positions=positions, return_kv=True,
                                 kv_chunk=kv_chunk)
        ym, maux = mamba_apply(p["mamba"], cfg, h)
        ya = norm_apply(p["fuse_ln_attn"], ya) * p["beta_attn"].astype(x.dtype)
        ym = norm_apply(p["fuse_ln_ssm"], ym) * p["beta_ssm"].astype(x.dtype)
        y = 0.5 * (ya + ym)
        if cache is not None:
            cache_out["attn"] = cache_write(cache["attn"], cfg, layer_idx,
                                            kk, vv, positions)
            cache_out["mamba"] = {"conv": maux["conv"], "h": maux["ssm"]}
    x = x + y

    if enc_kv is not None:
        h = norm_apply(p["ln_cross"], x, kind=cfg.norm_type)
        x = x + cross_attn_apply(p["cross"], cfg, h, enc_kv, enc_mask)

    h = norm_apply(p["ln2"], x, kind=cfg.norm_type)
    mlp_type = cfg.layer_mlp_type(layer_idx)
    if mlp_type == "dense":
        y = mlp_apply(p["mlp"], h, act=cfg.activation)
    elif mlp_type == "moe":
        y, metrics = moe_apply(p["moe"], cfg, h, full_capacity=moe_full_capacity)
    else:  # rwkv channel mix
        y, cm_aux = rwkv_cm_apply(p["cm"], cfg, h)
        if cache_out is not None:
            cache_out["tm"] = dict(cache_out["tm"], shift_cm=cm_aux["x_last"])
    x = x + y
    return x, metrics, cache_out


# ---------------------------------------------------------------------------
# Cached block forward (BPD verify substep: k fresh tokens)
# ---------------------------------------------------------------------------


def block_cached(p, cfg: ModelConfig, layer_idx: int, x, cache: Dict, length,
                 *, enc_kv=None, enc_mask=None, kv_chunk: int = 0, tree=None
                 ) -> Tuple[jnp.ndarray, Dict]:
    """x: (B, k, d) fresh tokens at positions length..length+k-1.

    Returns (y, new_cache).  Recurrent state entries in new_cache are stacked
    per-step (leading axis k) — ``commit_cache`` resolves them once k̂ is
    known.  Attention cache entries need no rollback (masking by position).

    ``tree`` (a ``kernels.tree_mask.TreeTopology``) switches the block to
    tree verification — attention-family layers only: recurrent states are
    conditioned on the whole previous chain step-by-step, so a branching
    block has no single per-step state to roll back to.
    """
    b, kblk, _ = x.shape
    new_cache = dict(cache)

    if tree is not None and cfg.block_type != "attn":
        raise NotImplementedError(
            f"tree verification requires pure attention blocks "
            f"(block_type='attn'); {cfg.block_type!r} carries chain-"
            f"conditioned per-step recurrent state")

    h = norm_apply(p["ln1"], x, kind=cfg.norm_type)
    if cfg.block_type == "attn":
        y, new_cache["attn"] = attn_cached(p["attn"], cfg, h, cache["attn"],
                                           length, layer_idx=layer_idx,
                                           kv_chunk=kv_chunk, tree=tree)
    elif cfg.block_type == "rwkv6":
        y, aux = rwkv_tm_apply(p["tm"], cfg, h,
                               x_prev=cache["tm"]["shift_tm"],
                               state0=cache["tm"]["state"],
                               return_states=True)
        # stacked per-step: shift = the h inputs themselves, state = aux
        new_cache["tm"] = {
            "shift_tm_steps": h,                       # (B,k,d)
            "state_steps": aux["state"],               # (B,k,H,D,D)
            "shift_tm": cache["tm"]["shift_tm"],
            "shift_cm": cache["tm"]["shift_cm"],
            "state": cache["tm"]["state"],
        }
    elif cfg.block_type == "hymba":
        ya, new_cache["attn"] = attn_cached(p["attn"], cfg, h, cache["attn"],
                                            length, layer_idx=layer_idx,
                                            kv_chunk=kv_chunk)
        ym, maux = mamba_apply(p["mamba"], cfg, h,
                               conv_state=cache["mamba"]["conv"],
                               h0=cache["mamba"]["h"], return_states=True)
        ya = norm_apply(p["fuse_ln_attn"], ya) * p["beta_attn"].astype(x.dtype)
        ym = norm_apply(p["fuse_ln_ssm"], ym) * p["beta_ssm"].astype(x.dtype)
        y = 0.5 * (ya + ym)
        new_cache["mamba"] = {
            "conv_steps": maux["conv"],                # (B,k,W-1,di)
            "h_steps": maux["ssm"],                    # (B,k,di,N)
            "conv": cache["mamba"]["conv"],
            "h": cache["mamba"]["h"],
        }
    x = x + y

    if enc_kv is not None:
        h = norm_apply(p["ln_cross"], x, kind=cfg.norm_type)
        x = x + cross_attn_apply(p["cross"], cfg, h, enc_kv, enc_mask)

    h = norm_apply(p["ln2"], x, kind=cfg.norm_type)
    mlp_type = cfg.layer_mlp_type(layer_idx)
    if mlp_type == "dense":
        y = mlp_apply(p["mlp"], h, act=cfg.activation)
    elif mlp_type == "moe":
        y, _ = moe_apply(p["moe"], cfg, h, full_capacity=True)
    else:
        y, _ = rwkv_cm_apply(p["cm"], cfg, h,
                             x_prev=cache["tm"]["shift_cm"])
        new_cache["tm"]["shift_cm_steps"] = h          # (B,k,d)
    x = x + y
    return x, new_cache


def commit_cache(cfg: ModelConfig, cache: Dict, khat) -> Dict:
    """Resolve stacked per-step recurrent states to the accepted prefix.

    khat: (B,) or () int32 in [0, k] — number of accepted tokens per row this
    iteration (0 = row already finished: keep the pre-iteration state).
    Attention caches are untouched (absolute-position masking handles
    rollback); recurrent states select step khat-1.
    """
    out = dict(cache)
    khat = jnp.asarray(khat, jnp.int32)

    def pick(steps, old):  # steps: (B, k, ...) old: (B, ...) -> (B, ...)
        b = steps.shape[0]
        kh = jnp.broadcast_to(khat, (b,))
        idx = jnp.maximum(kh - 1, 0).reshape((b,) + (1,) * (steps.ndim - 1))
        picked = jnp.take_along_axis(steps, idx, axis=1).squeeze(1)
        keep_old = (kh == 0).reshape((b,) + (1,) * (old.ndim - 1))
        return jnp.where(keep_old, old, picked.astype(old.dtype))

    if "tm" in cache:
        tm = cache["tm"]
        out["tm"] = {
            "shift_tm": pick(tm["shift_tm_steps"], tm["shift_tm"])
            if "shift_tm_steps" in tm else tm["shift_tm"],
            "shift_cm": pick(tm["shift_cm_steps"], tm["shift_cm"])
            if "shift_cm_steps" in tm else tm["shift_cm"],
            "state": pick(tm["state_steps"], tm["state"])
            if "state_steps" in tm else tm["state"],
        }
    if "mamba" in cache:
        mb = cache["mamba"]
        out["mamba"] = {
            "conv": pick(mb["conv_steps"], mb["conv"])
            if "conv_steps" in mb else mb["conv"],
            "h": pick(mb["h_steps"], mb["h"]) if "h_steps" in mb else mb["h"],
        }
    return out
