"""Step factories + input specs for training, prefill and BPD serving.

These are what the launcher jits and what the multi-pod dry-run lowers:

  * ``train_step``   — forward + BPD multi-head loss + optimizer update
  * ``prefill_step`` — parallel forward building the KV caches + the first
                       block proposals (the paper's initial predict substep)
  * ``serve_step``   — ONE blockwise-parallel-decoding iteration: k proposal
                       tokens verified (and re-proposed) against the cache
                       (paper §4 combined model; decode_32k / long_500k)

``input_specs(cfg, shape)`` returns ShapeDtypeStructs for every model input —
weak-type-correct, shardable, no device allocation.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import INPUT_SHAPES, DecodeConfig, ModelConfig, TrainConfig
from repro.core import decode as decode_lib
from repro.core.policy import resolve_policy
from repro.core.train import loss_fn_for
from repro.models import cache as cache_lib
from repro.models import model as model_lib
from repro.optim import optimizer_init, optimizer_update

F32, I32, BOOL = jnp.float32, jnp.int32, jnp.bool_


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins, no allocation)
# ---------------------------------------------------------------------------


def text_len_for(cfg: ModelConfig, seq_len: int) -> int:
    """Text positions = seq_len minus the modality/meta prefix."""
    n = cfg.num_meta_tokens
    if cfg.modality == "vision_text":
        n += cfg.num_patch_tokens
    return max(seq_len - n, 8)


def input_specs(cfg: ModelConfig, shape_name: str) -> Dict[str, jax.ShapeDtypeStruct]:
    """Model inputs for one entry of the assigned shape grid."""
    spec = INPUT_SHAPES[shape_name]
    b, s = spec["global_batch"], spec["seq_len"]
    sds = jax.ShapeDtypeStruct
    if cfg.modality == "audio":
        out = {"frame_embeds": sds((b, s, cfg.d_model), F32)}
        if spec["kind"] == "train":
            out["mask"] = sds((b, s), BOOL)
            out["targets"] = sds((b, s), I32)
        return out
    out = {"tokens": sds((b, text_len_for(cfg, s)), I32)}
    if cfg.modality == "vision_text":
        out["patch_embeds"] = sds((b, cfg.num_patch_tokens, cfg.d_model), F32)
    return out


# ---------------------------------------------------------------------------
# train_step
# ---------------------------------------------------------------------------


def make_train_step(cfg: ModelConfig, tc: TrainConfig,
                    mask: Any = None) -> Callable:
    loss_fn = loss_fn_for(cfg)

    def train_step(params, opt_state, batch, key):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(p, cfg, tc, batch, key), has_aux=True)(params)
        params, opt_state, opt_m = optimizer_update(grads, opt_state, params,
                                                    tc, mask=mask)
        metrics.update(opt_m)
        return params, opt_state, metrics

    return train_step


# ---------------------------------------------------------------------------
# prefill_step
# ---------------------------------------------------------------------------


def make_prefill_step(cfg: ModelConfig, dec: DecodeConfig,
                      *, kv_chunk: int = 0) -> Callable:
    if cfg.is_encoder_only:
        # encoder "prefill" = one full parallel encode producing code logits
        def encode_step(params, batch):
            h = model_lib.embed_inputs(params, cfg, batch)
            hidden, _, _ = model_lib.forward_hidden(params, cfg, h,
                                                    bidirectional=True,
                                                    kv_chunk=kv_chunk)
            return model_lib.project_vocab(params, cfg, hidden)

        return encode_step

    def prefill_step(params, batch):
        state, _ = decode_lib.bpd_prefill_causal_lm(
            params, cfg, dec, batch, max_new=dec.max_new_tokens,
            kv_chunk=kv_chunk)
        # return the serving state: caches + first proposals
        return state

    return prefill_step


# ---------------------------------------------------------------------------
# serve_step — one BPD iteration against an existing cache
# ---------------------------------------------------------------------------


def make_serve_step(cfg: ModelConfig, dec: DecodeConfig, *, seq_len: int,
                    max_new: int = 4096, kv_chunk: int = 0,
                    aux_params=None) -> Callable:
    """``aux_params`` ({bundle name: params}) is closed over for policies
    whose drafter runs an auxiliary model (see core.bundle); the default
    serve path is single-model."""
    prefix = cfg.num_meta_tokens + (
        cfg.num_patch_tokens if cfg.modality == "vision_text" else 0)
    backend = decode_lib.causal_lm_backend(cfg, kv_chunk=kv_chunk)
    pol = resolve_policy(dec)

    def serve_step(params, state: decode_lib.BPDState) -> decode_lib.BPDState:
        return decode_lib.bpd_iteration(
            params, cfg, dec, backend, state,
            prefix_offset=prefix, max_new=max_new, policy=pol,
            aux_params=aux_params)

    return serve_step


def serve_state_struct(cfg: ModelConfig, dec: DecodeConfig, *, batch: int,
                       seq_len: int, max_new: int = 4096):
    """ShapeDtypeStructs of the BPD serving state at context ``seq_len``.

    Includes the loop-carried policy state for ``dec``'s resolved policy
    (stateful schedules/drafters carry per-row arrays; the serve path is
    prompt-only, so drafters that need decode-entry inputs reject here).
    """
    block_k = dec.block_k or cfg.bpd_k
    prefix = cfg.num_meta_tokens + (
        cfg.num_patch_tokens if cfg.modality == "vision_text" else 0)
    pol = resolve_policy(dec)

    def mk():
        caches = model_lib.init_caches(cfg, batch, seq_len + max_new, block_k,
                                       backend=cache_lib.get_backend(dec))
        text_cap = seq_len - prefix + max_new + block_k
        return decode_lib.BPDState(
            tokens=jnp.zeros((batch, text_cap), I32),
            text_len=jnp.full((batch,), seq_len - prefix, I32),
            proposals=jnp.zeros((batch, block_k), I32),
            caches=caches,
            finished=jnp.zeros((batch,), bool),
            iters=jnp.zeros((), I32),
            generated=jnp.zeros((batch,), I32),
            policy_state=pol.init_state(cfg, dec, None, batch),
        )

    return jax.eval_shape(mk)


def materialize_serve_state(cfg: ModelConfig, dec: DecodeConfig, *, batch: int,
                            seq_len: int, max_new: int = 4096
                            ) -> decode_lib.BPDState:
    """Concrete (zeros) serving state — used by tests and local serving."""
    struct = serve_state_struct(cfg, dec, batch=batch, seq_len=seq_len,
                                max_new=max_new)
    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), struct)


# ---------------------------------------------------------------------------
# Shape-grid adaptation (DESIGN.md §5): which (arch × shape) pairs run, and
# with which config variant.
# ---------------------------------------------------------------------------

LONG_WINDOW = 8192


def adapt_config(cfg: ModelConfig, shape_name: str) -> Optional[ModelConfig]:
    """Returns the config variant for this shape, or None if the pair is
    skipped (recorded in DESIGN.md)."""
    kind = INPUT_SHAPES[shape_name]["kind"]
    if cfg.is_encoder_only and kind == "decode":
        return None  # no autoregressive decode exists
    if shape_name == "long_500k":
        sub_quadratic = (cfg.block_type in ("rwkv6", "hymba")
                         or cfg.sliding_window)
        if cfg.is_mla:
            return None  # latent attention has no sliding-window form
        if not sub_quadratic:
            # dense/MoE/VLM: sliding-window variant (flagged, approximate)
            return cfg.replace(sliding_window=LONG_WINDOW)
    return cfg
