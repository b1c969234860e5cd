"""Pallas TPU kernel: blockwise-parallel-decode *verify* attention.

The hot spot of BPD serving is scoring a tiny block of k fresh query tokens
(k = block size, ~2-16) against a long KV cache (32k-512k entries).  This is
the opposite regime from training flash-attention: Sq is tiny, Sk is huge, so
the kernel keeps the whole (padded) query block resident in VMEM and streams
the KV cache through in ``block_kv`` tiles with an online softmax
(flash-decoding style).

TPU adaptation (vs the paper's P100 setting, which had no custom kernel):
  * KV tiles are (block_kv, head_dim) with head_dim padded to a multiple of
    128 (lane width) and block_kv a multiple of 8 (sublane) — MXU-aligned.
  * GQA is folded into the query rows: the q block is (kq × G, hd) so the
    kernel row index encodes (query position, group member); the (tiny-q ×
    long-KV) matmul runs on the MXU without materializing repeated K/V.
  * Masking is positional: the cache carries an absolute position per slot
    (ring buffer), and the mask is recomputed from (q_pos, kv_pos) so BPD
    rollback (accepted length shrinking by up to k-1) costs no data movement.
    Stale speculative slots are marked with pos = -1 by the caller.
  * Sliding windows + hymba meta-token exemption are the same positional
    predicate used by the jnp oracle (``ref.verify_attention``).

Grid: (batch, kv_head, num_kv_blocks); the last axis is sequential on TPU so
the online-softmax carry lives in VMEM scratch across KV tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _verify_attn_kernel(qpos_ref, kvpos_ref, q_ref, k_ref, v_ref,  # inputs
                        o_ref,                                     # outputs
                        m_ref, l_ref, acc_ref,                     # scratch
                        *, group: int, window: int, num_meta: int,
                        scale: float):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (RQ = kq*G, hd)
    k = k_ref[0, 0].astype(jnp.float32)            # (block_kv, hd)
    v = v_ref[0, 0].astype(jnp.float32)            # (block_kv, hd)
    qp = qpos_ref[0]                               # (RQ, 1) int32 (row -> q pos)
    kp = kvpos_ref[0]                              # (1, block_kv) int32

    scores = jax.lax.dot_general(
        q * scale, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)        # (RQ, block_kv)

    mask = (kp >= 0) & (kp <= qp)
    if window:
        mask &= (qp - kp < window) | (kp < num_meta)
    scores = jnp.where(mask, scores, NEG_INF)

    m_prev = m_ref[...]                            # (RQ, 1)
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)                    # (RQ, block_kv)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kb == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _rows(x, g, rq_pad, fill):
    """(B, kq) per-query values -> (B, rq_pad, 1) per kernel row (row =
    q_idx * g + g_idx): a column block, so the kernel reads it (RQ, 1)."""
    rows = jnp.repeat(x.astype(jnp.int32), g, axis=1)
    rows = jnp.pad(rows, ((0, 0), (0, rq_pad - rows.shape[1])),
                   constant_values=fill)
    return rows[:, :, None]


def _cols(x, l_pad):
    """(B, L) per-slot values -> (B, 1, l_pad), padded slots -1 (masked):
    a row block, so the kernel reads it (1, block_kv)."""
    x = x.astype(jnp.int32)
    return jnp.pad(x, ((0, 0), (0, l_pad - x.shape[1])),
                   constant_values=-1)[:, None, :]


def verify_attention_pallas(q, k, v, q_pos, kv_pos, *, window: int = 0,
                            num_meta: int = 0, block_kv: int = 512,
                            interpret: bool = False) -> jnp.ndarray:
    """q: (B, kq, H, hd); k/v: (B, L, KV, hd); q_pos: (B, kq); kv_pos: (B, L).

    Returns (B, kq, H, hd).  Rows whose kv_pos is -1 are masked out.
    """
    b, kq, h, hd = q.shape
    l, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    scale = float(hd) ** -0.5

    # ---- fold GQA groups into query rows; pad for TPU tile alignment -------
    rq = kq * g
    rq_pad = max(8, ((rq + 7) // 8) * 8)
    hd_pad = max(128, ((hd + 127) // 128) * 128)
    block_kv = min(block_kv, ((l + 7) // 8) * 8)
    l_pad = ((l + block_kv - 1) // block_kv) * block_kv

    # head index h = kvh_idx * g + g_idx  (matches models.attention._gqa_attend)
    qr = q.reshape(b, kq, kvh, g, hd).transpose(0, 2, 1, 3, 4).reshape(b, kvh, rq, hd)
    qr = jnp.pad(qr, ((0, 0), (0, 0), (0, rq_pad - rq), (0, hd_pad - hd)))
    kr = jnp.pad(k.transpose(0, 2, 1, 3),
                 ((0, 0), (0, 0), (0, l_pad - l), (0, hd_pad - hd)))
    vr = jnp.pad(v.transpose(0, 2, 1, 3),
                 ((0, 0), (0, 0), (0, l_pad - l), (0, hd_pad - hd)))

    # per-row query positions (row = q_idx * g + g_idx)
    qpos_rows = _rows(q_pos, g, rq_pad, -(2 ** 30))             # (B, rq, 1)
    kvpos_p = _cols(kv_pos, l_pad)                              # (B, 1, L)

    grid = (b, kvh, l_pad // block_kv)
    out = pl.pallas_call(
        functools.partial(_verify_attn_kernel, group=g, window=window,
                          num_meta=num_meta, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, rq_pad, 1), lambda bi, hi, ki: (bi, 0, 0)),
            pl.BlockSpec((1, 1, block_kv), lambda bi, hi, ki: (bi, 0, ki)),
            pl.BlockSpec((1, 1, rq_pad, hd_pad), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, hd_pad), lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, hd_pad), lambda bi, hi, ki: (bi, hi, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rq_pad, hd_pad),
                               lambda bi, hi, ki: (bi, hi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kvh, rq_pad, hd_pad), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rq_pad, 1), jnp.float32),
            pltpu.VMEM((rq_pad, 1), jnp.float32),
            pltpu.VMEM((rq_pad, hd_pad), jnp.float32),
        ],
        interpret=interpret,
    )(qpos_rows, kvpos_p, qr, kr, vr)

    out = out[:, :, :rq, :hd].reshape(b, kvh, kq, g, hd)
    return out.transpose(0, 2, 1, 3, 4).reshape(b, kq, h, hd)


# ---------------------------------------------------------------------------
# Tree-verification variant
# ---------------------------------------------------------------------------
#
# Same streaming structure, but the query block is a *candidate tree* (see
# kernels.tree_mask): a node must attend the committed prefix plus exactly
# its ancestors-or-self inside the block.  The ancestor set rides along as a
# packed int32 bitmask per query row (bit n = node n visible), and KV slots
# carry a node index (-1 for prefix entries) so the kernel picks the bit
# test or the positional predicate per slot.  Positions are logical (RoPE)
# positions — prefix causality and sliding windows use them unchanged.


def _tree_verify_attn_kernel(qpos_ref, abits_ref, kvpos_ref, kvnode_ref,
                             q_ref, k_ref, v_ref,            # inputs
                             o_ref,                          # outputs
                             m_ref, l_ref, acc_ref,          # scratch
                             *, group: int, window: int, num_meta: int,
                             scale: float):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (RQ = kq*G, hd)
    k = k_ref[0, 0].astype(jnp.float32)            # (block_kv, hd)
    v = v_ref[0, 0].astype(jnp.float32)            # (block_kv, hd)
    qp = qpos_ref[0]                               # (RQ, 1) int32 logical pos
    abits = abits_ref[0]                           # (RQ, 1) int32 ancestor bits
    kp = kvpos_ref[0]                              # (1, block_kv) int32
    kn = kvnode_ref[0]                             # (1, block_kv) int32 (-1=prefix)

    scores = jax.lax.dot_general(
        q * scale, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)        # (RQ, block_kv)

    mask = (kp >= 0) & (kp <= qp)
    if window:
        mask &= (qp - kp < window) | (kp < num_meta)
    # tree slots additionally require the ancestor bit; ancestors sit at
    # shallower depth so (kp <= qp) already holds for every visible one
    bit = jax.lax.shift_right_logical(abits, jnp.clip(kn, 0, 31)) & 1
    mask &= (kn < 0) | (bit != 0)
    scores = jnp.where(mask, scores, NEG_INF)

    m_prev = m_ref[...]                            # (RQ, 1)
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)                    # (RQ, block_kv)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kb == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def tree_verify_attention_pallas(q, k, v, q_pos, kv_pos, kv_node, anc_bits, *,
                                 window: int = 0, num_meta: int = 0,
                                 block_kv: int = 512,
                                 interpret: bool = False) -> jnp.ndarray:
    """Tree-verification attention over a positional KV cache.

    q: (B, kq, H, hd) — the kq candidate-tree nodes; k/v: (B, L, KV, hd);
    q_pos: (B, kq) logical (RoPE) positions, i.e. length + depth[node];
    kv_pos: (B, L) logical positions (-1 = empty/stale);
    kv_node: (B, L) int32 — node index for slots holding this block's tree
    nodes, -1 for committed-prefix slots;
    anc_bits: (B, kq) int32 — packed ancestor-or-self bitmask per node
    (``TreeTopology.anc_bits``; ≤32 nodes).

    Returns (B, kq, H, hd).
    """
    b, kq, h, hd = q.shape
    l, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    scale = float(hd) ** -0.5

    rq = kq * g
    rq_pad = max(8, ((rq + 7) // 8) * 8)
    hd_pad = max(128, ((hd + 127) // 128) * 128)
    block_kv = min(block_kv, ((l + 7) // 8) * 8)
    l_pad = ((l + block_kv - 1) // block_kv) * block_kv

    qr = q.reshape(b, kq, kvh, g, hd).transpose(0, 2, 1, 3, 4).reshape(b, kvh, rq, hd)
    qr = jnp.pad(qr, ((0, 0), (0, 0), (0, rq_pad - rq), (0, hd_pad - hd)))
    kr = jnp.pad(k.transpose(0, 2, 1, 3),
                 ((0, 0), (0, 0), (0, l_pad - l), (0, hd_pad - hd)))
    vr = jnp.pad(v.transpose(0, 2, 1, 3),
                 ((0, 0), (0, 0), (0, l_pad - l), (0, hd_pad - hd)))

    qpos_rows = _rows(q_pos, g, rq_pad, -(2 ** 30))             # (B, rq, 1)
    abits_rows = _rows(anc_bits, g, rq_pad, 0)
    kvpos_p = _cols(kv_pos, l_pad)                              # (B, 1, L)
    kvnode_p = _cols(kv_node, l_pad)

    grid = (b, kvh, l_pad // block_kv)
    out = pl.pallas_call(
        functools.partial(_tree_verify_attn_kernel, group=g, window=window,
                          num_meta=num_meta, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, rq_pad, 1), lambda bi, hi, ki: (bi, 0, 0)),
            pl.BlockSpec((1, rq_pad, 1), lambda bi, hi, ki: (bi, 0, 0)),
            pl.BlockSpec((1, 1, block_kv), lambda bi, hi, ki: (bi, 0, ki)),
            pl.BlockSpec((1, 1, block_kv), lambda bi, hi, ki: (bi, 0, ki)),
            pl.BlockSpec((1, 1, rq_pad, hd_pad), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, hd_pad), lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, hd_pad), lambda bi, hi, ki: (bi, hi, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rq_pad, hd_pad),
                               lambda bi, hi, ki: (bi, hi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kvh, rq_pad, hd_pad), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rq_pad, 1), jnp.float32),
            pltpu.VMEM((rq_pad, 1), jnp.float32),
            pltpu.VMEM((rq_pad, hd_pad), jnp.float32),
        ],
        interpret=interpret,
    )(qpos_rows, abits_rows, kvpos_p, kvnode_p, qr, kr, vr)

    out = out[:, :, :rq, :hd].reshape(b, kvh, kq, g, hd)
    return out.transpose(0, 2, 1, 3, 4).reshape(b, kq, h, hd)
