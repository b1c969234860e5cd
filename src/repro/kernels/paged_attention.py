"""Pallas TPU kernel: BPD verify attention over a *paged* KV cache.

Same regime as ``block_attention`` — a tiny block of k fresh query tokens
scored against a long KV context — but the context lives in a shared pool
of fixed-size pages (``models.cache.paged_attn_cache_init``) instead of a
dense per-row slab.  Each slot addresses its context through a block table
``tbl (B, P)`` of physical page ids, so the kernel must gather pages rather
than stream a contiguous row.

TPU adaptation:
  * The block table is a *scalar-prefetch* argument
    (``pltpu.PrefetchScalarGridSpec``): it lands in SMEM before the body
    runs, and the K/V BlockSpec index maps read ``tbl[b, p]`` to aim each
    grid step's DMA at the right physical page.  The gather happens in the
    pipeline — no (B, P*ps) dense copy of the pool is ever materialized.
  * Grid is (batch, kv_head, page); the page axis is sequential on TPU so
    the flash-decoding online-softmax carry (m/l/acc) lives in VMEM scratch
    across pages, exactly as ``block_attention`` carries it across KV tiles.
    One KV tile == one page (``page_size`` is a multiple of 8 by
    EngineConfig validation, so tiles stay sublane-aligned).
  * GQA folds into query rows ((kq × G, hd) resident block), masking is the
    same positional predicate as the dense kernel: ``kv_pos`` is the slot's
    *logical* position array (B, P*ps), so CoW-shared pages and BPD
    rollback (pos = -1 staling) need no data movement — unmapped table
    entries point at trash page 0 and their positions are -1, masking the
    whole page.

Oracle: ``ref.paged_verify_attention`` (gather ``kp[tbl]`` + dense oracle).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_attn_kernel(tbl_ref,                                # scalar prefetch
                       qpos_ref, kvpos_ref, q_ref, k_ref, v_ref,  # inputs
                       o_ref,                                  # outputs
                       m_ref, l_ref, acc_ref,                  # scratch
                       *, window: int, num_meta: int, scale: float):
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (RQ = kq*G, hd)
    k = k_ref[0, 0].astype(jnp.float32)            # (page_size, hd)
    v = v_ref[0, 0].astype(jnp.float32)            # (page_size, hd)
    qp = qpos_ref[0]                               # (RQ, 1) int32 (row -> q pos)
    kp = kvpos_ref[0, 0]                           # (1, page_size) int32

    scores = jax.lax.dot_general(
        q * scale, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)        # (RQ, page_size)

    mask = (kp >= 0) & (kp <= qp)
    if window:
        mask &= (qp - kp < window) | (kp < num_meta)
    scores = jnp.where(mask, scores, NEG_INF)

    m_prev = m_ref[...]                            # (RQ, 1)
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)                    # (RQ, page_size)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(pi == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_verify_attention_pallas(q, kp, vp, tbl, q_pos, kv_pos, *,
                                  window: int = 0, num_meta: int = 0,
                                  interpret: bool = False) -> jnp.ndarray:
    """q: (B, kq, H, hd); kp/vp: (num_pages, ps, KV, hd); tbl: (B, P) i32;
    q_pos: (B, kq); kv_pos: (B, P*ps) logical positions (-1 = masked).

    Returns (B, kq, H, hd).  ``ps`` must be a multiple of 8 (sublane)."""
    b, kq, h, hd = q.shape
    num_pages, ps, kvh, _ = kp.shape
    P = tbl.shape[1]
    if ps % 8:
        raise ValueError(f"page_size {ps} must be a multiple of 8")
    if kv_pos.shape != (b, P * ps):
        raise ValueError(f"kv_pos shape {kv_pos.shape} != {(b, P * ps)}")
    g = h // kvh
    scale = float(hd) ** -0.5

    # ---- fold GQA groups into query rows; pad for TPU tile alignment -------
    rq = kq * g
    rq_pad = max(8, ((rq + 7) // 8) * 8)
    hd_pad = max(128, ((hd + 127) // 128) * 128)

    # head index h = kvh_idx * g + g_idx  (matches models.attention._gqa_attend)
    qr = q.reshape(b, kq, kvh, g, hd).transpose(0, 2, 1, 3, 4).reshape(b, kvh, rq, hd)
    qr = jnp.pad(qr, ((0, 0), (0, 0), (0, rq_pad - rq), (0, hd_pad - hd)))
    # pool laid out (page, kv_head, ps, hd) so each grid step's block is one
    # page of one kv head — (ps, hd_pad) MXU-aligned
    kr = jnp.pad(kp.transpose(0, 2, 1, 3),
                 ((0, 0), (0, 0), (0, 0), (0, hd_pad - hd)))
    vr = jnp.pad(vp.transpose(0, 2, 1, 3),
                 ((0, 0), (0, 0), (0, 0), (0, hd_pad - hd)))

    qpos_rows = jnp.repeat(q_pos, g, axis=1)                     # (B, rq)
    qpos_rows = jnp.pad(qpos_rows, ((0, 0), (0, rq_pad - rq)),
                        constant_values=-(2 ** 30))[:, :, None]
    # one (1, ps) row per (slot, page), so each block equals its array's
    # last two dims whatever the page size
    kvpos_pages = kv_pos.astype(jnp.int32).reshape(b, P, 1, ps)

    grid = (b, kvh, P)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                     # tbl: SMEM, feeds index maps
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, rq_pad, 1), lambda bi, hi, pi, tbl: (bi, 0, 0)),
            pl.BlockSpec((1, 1, 1, ps),
                         lambda bi, hi, pi, tbl: (bi, pi, 0, 0)),
            pl.BlockSpec((1, 1, rq_pad, hd_pad),
                         lambda bi, hi, pi, tbl: (bi, hi, 0, 0)),
            # the paged gather: DMA the physical page this slot maps here
            pl.BlockSpec((1, 1, ps, hd_pad),
                         lambda bi, hi, pi, tbl: (tbl[bi, pi], hi, 0, 0)),
            pl.BlockSpec((1, 1, ps, hd_pad),
                         lambda bi, hi, pi, tbl: (tbl[bi, pi], hi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rq_pad, hd_pad),
                               lambda bi, hi, pi, tbl: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rq_pad, 1), jnp.float32),
            pltpu.VMEM((rq_pad, 1), jnp.float32),
            pltpu.VMEM((rq_pad, hd_pad), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_attn_kernel, window=window,
                          num_meta=num_meta, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, rq_pad, hd_pad), q.dtype),
        interpret=interpret,
    )(tbl.astype(jnp.int32), qpos_rows, kvpos_pages, qr, kr, vr)

    out = out[:, :, :rq, :hd].reshape(b, kvh, kq, g, hd)
    return out.transpose(0, 2, 1, 3, 4).reshape(b, kq, h, hd)
