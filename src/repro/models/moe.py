"""Mixture-of-Experts MLP: top-k routing, then one of two dispatches.

* No-drop (``full_capacity=True``: the serving engine's verify step and
  admissions, ``bpd_decode``/``greedy_decode`` prefills, the draft model):
  the B·S·K assignments are stably sorted by expert, their token rows
  gathered in that order, and the experts run as three grouped products
  (``kernels.ops.grouped_matmul``) over exactly those rows; the combine
  gathers them back and takes each token's gate-weighted sum.  Nothing is
  dropped, as blockwise parallel decoding's greedy equivalence needs.
* Capacity-bounded (training): GShard's per-row buffer of (B, Ep, Cg, d),
  built by a sort-based rank and index gathers (MaxText / MegaBlocks
  style) rather than the O(T·E·C) one-hot einsum, which at train_4k scale
  (1M tokens, 60 experts) is petabytes.  Assignments past an expert's
  capacity are dropped; with experts sharded over the ``model`` mesh axis
  GSPMD lowers the buffer's redistribution to an all-to-all on (B, Ep).

Covers the registered MoE architectures:
  * qwen2-moe-a2.7b:  60 routed experts top-4 + 4 shared experts (sigmoid gate)
  * olmoe-1b-7b:      64 routed experts top-8, no shared experts
  * deepseek-v2-lite: 64 routed experts top-6 without renormalisation
                      (``norm_topk_prob`` false, gates times
                      ``routed_scaling_factor``) + 2 ungated shared experts

Named scopes ``moe.route``, ``moe.dispatch``, ``moe.experts``,
``moe.combine`` and ``moe.shared`` mark the parts in a device trace.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.kernels.ops import grouped_matmul
from repro.models.layers import GATED_ACTIVATIONS, activation, dense_apply, dense_init
from repro.sharding.policy import maybe_shard_expert


def moe_init(key, cfg: ModelConfig, *, dtype=jnp.float32) -> Dict:
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    ep = cfg.padded_num_experts      # expert weights padded so E shards
    ks = jax.random.split(key, 6)
    std = (1.0 / jnp.sqrt(d).astype(jnp.float32)).astype(dtype)
    p = {
        "router": dense_init(ks[0], d, e, dtype=dtype),
        "w1": jax.random.normal(ks[1], (ep, d, ff), dtype) * std,
        "w2": jax.random.normal(ks[2], (ep, ff, d), dtype) * (1.0 / jnp.sqrt(ff)),
    }
    if cfg.activation in GATED_ACTIVATIONS:
        p["w3"] = jax.random.normal(ks[3], (ep, d, ff), dtype) * std
    if cfg.num_shared_experts:
        sff = cfg.shared_expert_d_ff or cfg.num_shared_experts * ff
        p["shared"] = {
            "w1": dense_init(ks[4], d, sff, dtype=dtype),
            "w3": dense_init(ks[5], d, sff, dtype=dtype),
            "w2": dense_init(jax.random.fold_in(key, 7), sff, d, dtype=dtype),
        }
        if cfg.shared_expert_gated:
            p["shared"]["gate"] = dense_init(jax.random.fold_in(key, 8), d, 1,
                                             dtype=dtype)
    return p


def _expert_ffn(p, x, act: str):
    """x: (B, E, C, d) -> (B, E, C, d), per-expert weights."""
    h = jnp.einsum("becd,edf->becf", x, p["w1"].astype(x.dtype))
    if "w3" in p:
        h = activation("silu" if act == "geglu" else act, h) * jnp.einsum(
            "becd,edf->becf", x, p["w3"].astype(x.dtype))
    else:
        h = activation(act, h)
    return jnp.einsum("becf,efd->becd", h, p["w2"].astype(x.dtype))


def _assignment_ranks(expert_ids_flat: jnp.ndarray) -> jnp.ndarray:
    """rank[a] = #{a' < a : expert[a'] == expert[a]} without O(A·E) one-hots.

    Sort assignments by expert (stable), compute position-within-segment via a
    cummax of segment starts, scatter back to assignment order.
    """
    a = expert_ids_flat.shape[0]
    order = jnp.argsort(expert_ids_flat, stable=True)
    sorted_e = expert_ids_flat[order]
    idx = jnp.arange(a, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_e[1:] != sorted_e[:-1]])
    seg_start = jnp.where(is_start, idx, 0)
    seg_start = jax.lax.associative_scan(jnp.maximum, seg_start)
    rank_sorted = idx - seg_start
    return jnp.zeros((a,), jnp.int32).at[order].set(rank_sorted)


def _capacity_dispatch(p, cfg: ModelConfig, x, expert_ids, gate_vals):
    """The capacity-bounded path: x (B, S, d) -> (y, keep).

    Each batch row is a dispatch group with its own capacity, so the expert
    buffer is (B, Ep, Cg, d), batch-sharded over `data` and expert-sharded
    over `model`; assignments past an expert's capacity are dropped
    (``keep`` false).
    """
    b, s, d = x.shape
    e, topk = cfg.num_experts, cfg.num_experts_per_tok
    ep = cfg.padded_num_experts
    capacity = min(int(max(1, cfg.capacity_factor * topk * s / e)), s)

    def dispatch_row(xr, er):
        """xr: (S, d); er: (S, K) -> per-group expert buffer + slots.

        The buffer is gathered: each of its Ep*Cg rows names the token that
        fills it (kept slots are distinct), or a zero row.  The TPU compile
        keeps a gather's ``op_name`` (a scatter-add and its zero fill lose
        theirs), so a trace charges the buffer to ``moe.dispatch``.
        """
        rank = _assignment_ranks(er.reshape(s * topk)).reshape(s, topk)
        keep = rank < capacity
        # destination in the (Ep*Cg) buffer; capacity overflow -> dump row
        slot = jnp.where(keep, er * capacity + rank, ep * capacity)
        src = jnp.full((ep * capacity + 1,), s, jnp.int32).at[
            slot.reshape(-1)].set(jnp.repeat(jnp.arange(s, dtype=jnp.int32),
                                             topk))
        xpad = jnp.concatenate([xr, jnp.zeros((1, d), xr.dtype)], 0)
        xin = jnp.take(xpad, src[: ep * capacity], axis=0)
        return xin.reshape(ep, capacity, d), slot, keep

    with jax.named_scope("moe.dispatch"):
        xin, slot, keep = jax.vmap(dispatch_row)(x, expert_ids)  # (B,Ep,Cg,d)
        xin = maybe_shard_expert(xin)

    with jax.named_scope("moe.experts"):
        xout = _expert_ffn(p, xin, cfg.activation)            # (B, Ep, Cg, d)
        xout = maybe_shard_expert(xout)

    def gather_row(xo, sl, gv, kp):
        flat = jnp.concatenate(
            [xo.reshape(ep * capacity, d), jnp.zeros((1, d), xo.dtype)], 0)
        g = jnp.take(flat, sl.reshape(-1), axis=0).reshape(s, topk, d)
        w = (gv * kp.astype(gv.dtype)).astype(xo.dtype)
        return jnp.einsum("skd,sk->sd", g, w)

    with jax.named_scope("moe.combine"):
        y = jax.vmap(gather_row)(xout, slot, gate_vals, keep)  # (B, S, d)
    return y, keep


def _grouped_expert_ffn(p, x, group_sizes, act: str):
    """x: (A, d) rows sorted by expert, group_sizes: (Ep,) -> (A, d); each
    row through its own expert's weights as three grouped products."""
    def mm(a, w):
        return grouped_matmul(a, w, group_sizes)

    h = mm(x, p["w1"])
    if "w3" in p:
        h = activation("silu" if act == "geglu" else act, h) * mm(x, p["w3"])
    else:
        h = activation(act, h)
    return mm(h, p["w2"])


def _sorted_dispatch(x, expert_ids, ep: int):
    """x: (B, S, d); expert_ids: (B, S, K) -> (rows, inverse, group_sizes).

    rows (B·S·K, d) holds each assignment's token, stably sorted by expert;
    ``rows[inverse]`` is back in assignment order; group_sizes (Ep,) counts
    each expert's rows (0 for padded experts).  Gathers keep their
    ``op_name`` through the TPU compile, so a trace charges them here.
    """
    topk = expert_ids.shape[-1]
    flat = expert_ids.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    group_sizes = jnp.bincount(flat, length=ep).astype(jnp.int32)
    rows = jnp.take(x.reshape(-1, x.shape[-1]), order // topk, axis=0)
    return rows, inverse, group_sizes


def moe_apply(p, cfg: ModelConfig, x, *, full_capacity: bool = False
              ) -> Tuple[jnp.ndarray, Dict]:
    """x: (B, S, d) -> (y, metrics).

    full_capacity=True takes the no-drop path: every assignment reaches its
    expert through the token-sorted grouped dispatch (B·S·K expert rows,
    however the tokens route).  Inference calls it so, since dropping would
    break the paper's greedy-equivalence guarantee for blockwise parallel
    decoding.  False (training) takes the capacity-bounded GShard buffer,
    batch-sharded over `data` and expert-sharded over `model`, whose
    data→expert redistribution lowers to an all-to-all on those two dims
    instead of replicating the token array per expert shard (measured:
    2.56 TB → GB-scale collectives at prefill_32k; EXPERIMENTS.md §Perf #3).
    """
    b, s, d = x.shape
    e, topk = cfg.num_experts, cfg.num_experts_per_tok

    with jax.named_scope("moe.route"):
        logits = dense_apply(p["router"], x.astype(jnp.float32))     # (B, S, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, topk)           # (B, S, K)
        if cfg.norm_topk_prob:
            gate_vals = gate_vals / jnp.maximum(
                jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)    # renorm
        elif cfg.routed_scaling_factor != 1.0:
            # DeepSeek-V2 scales the gates only where it does not renormalise
            gate_vals = gate_vals * cfg.routed_scaling_factor

    if full_capacity:
        with jax.named_scope("moe.dispatch"):
            xs, inverse, group_sizes = _sorted_dispatch(
                x, expert_ids, cfg.padded_num_experts)
        with jax.named_scope("moe.experts"):
            ys = _grouped_expert_ffn(p, xs, group_sizes, cfg.activation)
        with jax.named_scope("moe.combine"):
            g = jnp.take(ys, inverse, axis=0).reshape(b, s, topk, d)
            y = jnp.einsum("bskd,bsk->bsd", g, gate_vals.astype(x.dtype))
        keep = jnp.ones(expert_ids.shape, bool)
    else:
        y, keep = _capacity_dispatch(p, cfg, x, expert_ids, gate_vals)

    if "shared" in p:
        with jax.named_scope("moe.shared"):
            sp = p["shared"]
            h = (activation("silu", dense_apply(sp["w1"], x))
                 * dense_apply(sp["w3"], x))
            shared_out = dense_apply(sp["w2"], h)
            if "gate" in sp:
                g = jax.nn.sigmoid(dense_apply(sp["gate"], x).astype(
                    jnp.float32)).astype(x.dtype)
                shared_out = g * shared_out
            y = y + shared_out

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
