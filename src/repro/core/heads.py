"""Combined scoring-and-proposal heads (paper §4, §6, Fig. 3).

A single feedforward layer with hidden size k·d_hidden and output size
k·d_model is inserted after the decoder output; a residual connection feeds
the decoder output into each of the k outputs; the original vocabulary
projection is applied identically to each output, yielding the logits of
p_1 .. p_k.

Per the paper's footnote 1, transforming p_1 through a learned head makes
the combined model's greedy output differ slightly from the base model's;
using the identity for p_1 (``identity_p1=True``, our default) keeps p_1
exactly the base model.  Either way, blockwise parallel decoding with exact
verification reproduces greedy decoding *of p_1* — the paper's guarantee.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.config import ModelConfig


def heads_init(key, cfg: ModelConfig, *, dtype=jnp.float32) -> Dict:
    d = cfg.d_model
    k = cfg.bpd_k
    dh = cfg.resolved_bpd_hidden
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (d, k, dh), dtype) * (d ** -0.5),
        "b1": jnp.zeros((k, dh), dtype),
        "w2": jax.random.normal(k2, (k, dh, d), dtype) * (dh ** -0.5) * 0.1,
        "b2": jnp.zeros((k, d), dtype),
    }


def heads_apply(p, cfg: ModelConfig, hidden, *, identity_p1: bool = True,
                start: int = 0, stop=None) -> jnp.ndarray:
    """hidden: (..., d) -> (..., n, d) decoder outputs of heads
    ``start .. stop-1`` (static; all k by default).

    The first layer runs every head: a slice of ``w1`` along its head axis
    (its middle one) is a copy of those weights on the TPU, dearer than
    the rows it leaves out.  The second layer reads only the chosen heads'
    ``w2`` (a slice of its leading axis reads in place)."""
    heads = slice(start, stop)
    h = jnp.einsum("...d,dkh->...kh", hidden, p["w1"].astype(hidden.dtype))
    h = jax.nn.relu(h + p["b1"].astype(hidden.dtype))[..., heads, :]
    out = jnp.einsum("...kh,khd->...kd", h,
                     p["w2"][heads].astype(hidden.dtype))
    out = out + p["b2"][heads].astype(hidden.dtype) + hidden[..., None, :]
    if identity_p1 and start == 0:
        out = out.at[..., 0, :].set(hidden)
    return out


def head_apply_single(p, cfg: ModelConfig, hidden, head_idx: int, *,
                      identity_p1: bool = True) -> jnp.ndarray:
    """Only head ``head_idx`` (static int) — used by the paper's §6 training
    scheme (one random sub-loss per minibatch) to avoid materializing all k
    logit tensors."""
    if identity_p1 and head_idx == 0:
        return hidden
    w1 = p["w1"][:, head_idx].astype(hidden.dtype)
    b1 = p["b1"][head_idx].astype(hidden.dtype)
    w2 = p["w2"][head_idx].astype(hidden.dtype)
    b2 = p["b2"][head_idx].astype(hidden.dtype)
    h = jax.nn.relu(hidden @ w1 + b1)
    return h @ w2 + b2 + hidden


def head_apply_dynamic(p, cfg: ModelConfig, hidden, head_idx, *,
                       identity_p1: bool = True,
                       detach_residual: bool = False) -> jnp.ndarray:
    """Like head_apply_single but with a traced head index (training picks a
    random head per step inside jit).  identity_p1 is applied with a
    jnp.where on head_idx == 0.

    detach_residual stops the gradient through the ``+ hidden`` residual of
    the future heads (values unchanged).  Rationale: the residual feeds
    ``hidden`` straight into the shared vocab projection under a FUTURE-token
    loss, so its gradient coherently drags proj(hidden) — which IS p_1 —
    toward predicting t+i; at small scale this collapses p_1 within a few
    hundred steps (measured in EXPERIMENTS.md §Paper-claims).  Detaching it
    routes head gradients into the trunk only through the per-head FFN."""
    w1 = jnp.take(p["w1"], head_idx, axis=1).astype(hidden.dtype)   # (d, dh)
    b1 = jnp.take(p["b1"], head_idx, axis=0).astype(hidden.dtype)
    w2 = jnp.take(p["w2"], head_idx, axis=0).astype(hidden.dtype)   # (dh, d)
    b2 = jnp.take(p["b2"], head_idx, axis=0).astype(hidden.dtype)
    h = jax.nn.relu(hidden @ w1 + b1)
    res = jax.lax.stop_gradient(hidden) if detach_residual else hidden
    out = h @ w2 + b2 + res
    if identity_p1:
        out = jnp.where(head_idx == 0, hidden, out)
    return out
