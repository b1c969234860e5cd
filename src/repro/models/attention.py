"""GQA attention with RoPE, sliding windows, and BPD-aware KV caching.

Three entry points:
  * ``attn_full``    — parallel forward over a whole sequence (training /
                       prefill / encoder).  Optionally returns post-RoPE K/V
                       so prefill can populate the cache.
  * ``attn_cached``  — scores a block of ``k`` fresh tokens against the KV
                       cache *and* each other (the paper's verify substep).
  * ``cross_attn``   — encoder-decoder cross attention (paper's MT setting).

Masking is computed from absolute positions so the blockwise-parallel-decode
rollback ("length decreases by up to k-1") needs no data movement.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.layers import apply_rope, dense_init, norm_apply, norm_init

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def attn_init(key, cfg: ModelConfig, *, dtype=jnp.float32, cross: bool = False) -> Dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], d, h * hd, dtype=dtype)["w"].reshape(d, h, hd),
        "wk": dense_init(ks[1], d, kv * hd, dtype=dtype)["w"].reshape(d, kv, hd),
        "wv": dense_init(ks[2], d, kv * hd, dtype=dtype)["w"].reshape(d, kv, hd),
        "wo": dense_init(ks[3], h * hd, d, dtype=dtype)["w"].reshape(h, hd, d),
    }
    if cfg.qk_norm:
        p["q_norm"] = norm_init(hd, kind="rmsnorm", dtype=dtype)
        p["k_norm"] = norm_init(hd, kind="rmsnorm", dtype=dtype)
    return p


# ---------------------------------------------------------------------------
# Projections
# ---------------------------------------------------------------------------


def _project_qkv(p, cfg: ModelConfig, x, positions, *, rope: bool = True):
    """x: (B, S, d) -> q (B,S,H,hd), k/v (B,S,KV,hd); RoPE applied."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if "q_norm" in p:
        q = norm_apply(p["q_norm"], q)
        k = norm_apply(p["k_norm"], k)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _out_proj(p, ctx):
    """ctx: (B, S, H, hd) -> (B, S, d)."""
    return jnp.einsum("bshk,hkd->bsd", ctx, p["wo"].astype(ctx.dtype))


# ---------------------------------------------------------------------------
# Core scored attention (GQA without materializing repeated KV)
# ---------------------------------------------------------------------------


def _gqa_attend(q, k, v, mask, *, head_dim: int):
    """q: (B,Sq,H,hd)  k/v: (B,Sk,KV,hd)  mask: broadcastable to (B,Sq,Sk).

    Returns (B, Sq, H, hd).
    """
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, hd)
    scores = jnp.einsum("bqhgk,bshk->bhgqs", qg, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(head_dim))
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    ctx = jnp.einsum("bhgqs,bshk->bqhgk", probs, v)
    return ctx.reshape(b, sq, h, hd)


def make_causal_mask(q_pos, kv_pos, *, window: int = 0, num_meta: int = 0,
                     bidirectional: bool = False):
    """q_pos: (..., Sq), kv_pos: (..., Sk) absolute positions ->
    (..., Sq, Sk) bool.  Leading dims broadcast (per-row decode positions)."""
    q = q_pos[..., :, None]
    s = kv_pos[..., None, :]
    valid = s >= 0
    if bidirectional:
        m = valid & (q >= -1)  # broadcast q into the shape
    else:
        m = valid & (s <= q)
        if window:
            m = m & ((q - s < window) | (s < num_meta))
    return m


# ---------------------------------------------------------------------------
# Full-sequence forward (training / prefill / encoder)
# ---------------------------------------------------------------------------


def attn_full(p, cfg: ModelConfig, x, *, layer_idx: int = 0, positions=None,
              bidirectional: bool = False, return_kv: bool = False,
              kv_chunk: int = 0):
    """Parallel attention over the full sequence.

    kv_chunk > 0 switches to a memory-bounded chunked (flash-style) softmax —
    used for long-context prefill where the (Sq, Sk) score matrix would not
    fit; this is also the jnp oracle for the Pallas block-attention kernel.
    """
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)
    q, k, v = _project_qkv(p, cfg, x, positions, rope=not bidirectional)
    window = 0 if (bidirectional or layer_idx in cfg.global_attn_layers) else cfg.sliding_window
    if kv_chunk:
        ctx = _chunked_attend(q, k, v, positions, positions,
                              window=window, num_meta=cfg.num_meta_tokens,
                              bidirectional=bidirectional,
                              head_dim=cfg.resolved_head_dim, chunk=kv_chunk)
    else:
        mask = make_causal_mask(positions, positions, window=window,
                                num_meta=cfg.num_meta_tokens,
                                bidirectional=bidirectional)[None]
        ctx = _gqa_attend(q, k, v, mask, head_dim=cfg.resolved_head_dim)
    y = _out_proj(p, ctx)
    if return_kv:
        return y, (k, v)
    return y


def _chunked_attend(q, k, v, q_pos, kv_pos, *, window, num_meta, bidirectional,
                    head_dim, chunk):
    """Online-softmax attention, scanning KV in chunks of ``chunk``."""
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    kvh = k.shape[2]
    g = h // kvh
    q_pos = jnp.broadcast_to(q_pos, (b, sq))
    kv_pos = jnp.broadcast_to(kv_pos, (b, sk))
    qg = (q.reshape(b, sq, kvh, g, hd).astype(jnp.float32)
          / jnp.sqrt(jnp.float32(head_dim)))
    nchunks = (sk + chunk - 1) // chunk
    pad = nchunks * chunk - sk
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    pp = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=-1)
    kc = kp.reshape(b, nchunks, chunk, kvh, hd).transpose(1, 0, 2, 3, 4)
    vc = vp.reshape(b, nchunks, chunk, kvh, hd).transpose(1, 0, 2, 3, 4)
    pc = pp.reshape(b, nchunks, chunk).transpose(1, 0, 2)

    def body(carry, inp):
        m, l, acc = carry  # (B,KV,G,Sq), (B,KV,G,Sq), (B,KV,G,Sq,hd)
        kb, vb, pb = inp
        scores = jnp.einsum("bqhgk,bshk->bhgqs", qg, kb.astype(jnp.float32))
        mask = make_causal_mask(q_pos, pb, window=window, num_meta=num_meta,
                                bidirectional=bidirectional)  # (B, Sq, chunk)
        scores = jnp.where(mask[:, None, None], scores, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        alpha = jnp.exp(m - m_new)
        pexp = jnp.exp(scores - m_new[..., None])
        l_new = l * alpha + jnp.sum(pexp, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhgqs,bshk->bhgqk", pexp, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    init = (
        jnp.full((b, kvh, g, sq), NEG_INF, jnp.float32),
        jnp.zeros((b, kvh, g, sq), jnp.float32),
        jnp.zeros((b, kvh, g, sq, hd), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(body, init, (kc, vc, pc))
    ctx = acc / jnp.maximum(l, 1e-30)[..., None]
    ctx = ctx.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hd)
    return ctx.astype(q.dtype)


# ---------------------------------------------------------------------------
# Cache plumbing
# ---------------------------------------------------------------------------


def _slot_for(pos, buf_len: int, num_reserved: int):
    """Ring-buffer slot assignment with reserved leading (meta-token) slots."""
    ring = buf_len - num_reserved
    wrapped = num_reserved + jnp.remainder(pos - num_reserved, ring)
    return jnp.where(pos < num_reserved, pos, wrapped).astype(jnp.int32)


def _reserved_slots(cfg: ModelConfig, layer_idx: int, buf_len: int) -> int:
    window = 0 if layer_idx in cfg.global_attn_layers else cfg.sliding_window
    return cfg.num_meta_tokens if window else 0


def _paged_cache_write(cache: Dict, k, v, positions) -> Dict:
    """Scatter K/V through the block table into the page pool.

    Paged layers are always full-attention (windowed layers stay dense), so
    the slot assignment is the identity: position p lives in logical page
    ``p // page_size``, offset ``p % page_size``, and the block table maps
    logical to physical pages per row.  Rows whose table entry is 0 (trash
    page — evicted slots, unmapped tail pages) write harmlessly into page 0;
    its contents are never visible because the corresponding ``pos`` lanes
    mask out of every attention.
    """
    kp, vp, tbl = cache["kp"], cache["vp"], cache["tbl"]
    num_pages, ps, kvh, hd = kp.shape
    b = tbl.shape[0]
    if positions.ndim == 1:
        positions = jnp.broadcast_to(positions[None, :],
                                     (b, positions.shape[0]))
    positions = positions.astype(jnp.int32)
    s = positions.shape[1]
    phys = tbl[jnp.arange(b)[:, None], positions // ps] * ps + positions % ps
    new = dict(cache)
    new["kp"] = kp.reshape(num_pages * ps, kvh, hd).at[phys.reshape(-1)].set(
        k.reshape(b * s, kvh, hd).astype(kp.dtype)).reshape(kp.shape)
    new["vp"] = vp.reshape(num_pages * ps, kvh, hd).at[phys.reshape(-1)].set(
        v.reshape(b * s, kvh, hd).astype(vp.dtype)).reshape(vp.shape)
    new["pos"] = jax.vmap(lambda buf, slot, val: buf.at[slot].set(val))(
        cache["pos"], positions, positions)
    return new


def cache_kv_view(cache: Dict) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The (B, L, KV, hd) K/V arrays attention scores against — a direct
    reference for dense layers, a page gather for paged layers (the jnp
    path; ``kernels/paged_attention.py`` streams pages instead on TPU)."""
    if "kp" in cache:
        kp, vp, tbl = cache["kp"], cache["vp"], cache["tbl"]
        _, ps, kvh, hd = kp.shape
        b, P = tbl.shape
        return (kp[tbl].reshape(b, P * ps, kvh, hd),
                vp[tbl].reshape(b, P * ps, kvh, hd))
    return cache["k"], cache["v"]


def cache_write(cache: Dict, cfg: ModelConfig, layer_idx: int, k, v, positions) -> Dict:
    """Scatter post-RoPE K/V for ``positions`` into the ring buffer (dense)
    or through the block table (paged).

    positions: (S,) shared across rows (prefill) or (B, S) per-row (decode).
    """
    if "kp" in cache:
        return _paged_cache_write(cache, k, v, positions)
    buf_len = cache["k"].shape[1]
    b = cache["k"].shape[0]
    nres = _reserved_slots(cfg, layer_idx, buf_len)

    if positions.ndim == 1:
        s = positions.shape[0]
        if s > buf_len:
            # prefill longer than the window: keep the reserved (meta) head
            # plus the last (buf_len - nres) positions — everything else
            # would be overwritten anyway, and slicing keeps scatter indices
            # unique.
            keep = buf_len - nres
            if nres:
                cache = cache_write(cache, cfg, layer_idx, k[:, :nres],
                                    v[:, :nres], positions[:nres])
            k, v, positions = k[:, -keep:], v[:, -keep:], positions[-keep:]
        # one write path for prefill and decode: the TPU compiler aborts
        # (scatter_emitter) on the shared-index k/v/pos scatter of a long
        # prefill, and takes the per-row form
        positions = jnp.broadcast_to(positions[None, :],
                                     (b, positions.shape[0]))

    # per-row write: positions (B, S)
    slots = _slot_for(positions, buf_len, nres)                    # (B, S)

    def row_write(buf, slot, val):
        return buf.at[slot].set(val)

    new = dict(cache)
    new["k"] = jax.vmap(row_write)(cache["k"], slots, k.astype(cache["k"].dtype))
    new["v"] = jax.vmap(row_write)(cache["v"], slots, v.astype(cache["v"].dtype))
    new["pos"] = jax.vmap(row_write)(cache["pos"], slots,
                                     positions.astype(jnp.int32))
    return new


def attn_cached(p, cfg: ModelConfig, x_block, cache: Dict, length, *,
                layer_idx: int = 0, kv_chunk: int = 0,
                tree=None) -> Tuple[jnp.ndarray, Dict]:
    """Verify-substep attention: ``k`` fresh tokens vs the cache and each other.

    x_block : (B, k, d) tokens at absolute positions length .. length+k-1
    length  : (B,) or () int32 — number of *accepted* tokens per row.  Cache
              entries with pos >= length+k are stale speculative writes from
              rows that advanced differently and are masked out; entries in
              [length, length+k) are overwritten by this call's own write.
    tree    : optional ``kernels.tree_mask.TreeTopology`` — the block is a
              draft *tree* of ``k`` nodes instead of a chain.  Node n still
              writes its KV at storage position ``length + n`` (so the
              cache layout, slot math, and rollback masking are unchanged),
              but RoPE runs at the node's *logical* position
              ``length + depth[n]`` and the intra-block mask columns are
              overridden with the static ancestor matrix, so each node
              attends exactly to its root-to-node chain plus the committed
              cache.  After acceptance ``tree_commit_attn`` compacts the
              chosen root-to-leaf path back into chain slots.
    """
    b, kblk, _ = x_block.shape
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (b,))
    positions = length[:, None] + jnp.arange(kblk, dtype=jnp.int32)[None, :]
    if tree is None:
        rope_pos = positions
    else:
        if kv_chunk:
            raise ValueError(
                "tree verification is incompatible with kv_chunk (chunked "
                "attention has no per-column mask override); use the dense "
                "mask path for tree-drafted decode")
        if tree.num_nodes != kblk:
            raise ValueError(
                f"tree topology has {tree.num_nodes} nodes but the block "
                f"has {kblk} slots")
        depth = jnp.asarray(tree.depths)
        rope_pos = length[:, None] + depth[None, :]
    q, k, v = _project_qkv(p, cfg, x_block, rope_pos)
    cache = cache_write(cache, cfg, layer_idx, k, v, positions)
    window = 0 if layer_idx in cfg.global_attn_layers else cfg.sliding_window
    kv_pos = cache["pos"]                                          # (B, L)
    kv_pos = jnp.where(kv_pos < (length + kblk)[:, None], kv_pos, -1)
    ck, cv = cache_kv_view(cache)
    if kv_chunk:
        ctx = _chunked_attend(q, ck, cv, positions, kv_pos,
                              window=window, num_meta=cfg.num_meta_tokens,
                              bidirectional=False,
                              head_dim=cfg.resolved_head_dim, chunk=kv_chunk)
    else:
        mask = make_causal_mask(rope_pos, kv_pos, window=window,
                                num_meta=cfg.num_meta_tokens)       # (B, k, L)
        if tree is not None:
            # this block's entries sit at KV-view columns == their storage
            # slots; override those columns with ancestor ∧ window masking
            # computed on the nodes' logical positions
            intra = (jnp.asarray(tree.anc_matrix)[None]
                     & make_causal_mask(rope_pos, rope_pos, window=window,
                                        num_meta=cfg.num_meta_tokens))
            if "kp" in cache:
                cols = positions           # paged view column == position
            else:
                buf_len = cache["k"].shape[1]
                nres = _reserved_slots(cfg, layer_idx, buf_len)
                cols = _slot_for(positions, buf_len, nres)
            mask = jax.vmap(lambda m, s, iv: m.at[:, s].set(iv))(
                mask, cols, intra)
        ctx = _gqa_attend(q, ck, cv, mask,
                          head_dim=cfg.resolved_head_dim)
    return _out_proj(p, ctx), cache


def tree_commit_attn(cache: Dict, cfg: ModelConfig, layer_idx: int,
                     path_nodes, khat, length, block_k: int) -> Dict:
    """Compact an accepted root-to-leaf tree path into chain slots.

    After a tree verify forward, the KV for the token committed at position
    ``length + j`` lives at storage position ``length + path_nodes[:, j]``
    (the path's node at depth j — its RoPE position is already correct,
    since depth[path_nodes[:, j]] == j).  This gathers those entries and
    rewrites the leading ``khat`` chain slots so subsequent iterations see
    an ordinary committed chain; slots at j >= k̂ keep their speculative
    entries, which the next block overwrites exactly like chain decode.

    path_nodes : (B, k) int32 — node id at depth j (< 0 beyond the path)
    khat       : (B,) int32 accepted tokens; 0 = frozen row (no writes)
    length     : (B,) or () int32 pre-accept lengths (the block's base)
    """
    b = path_nodes.shape[0]
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (b,))
    j = jnp.arange(block_k, dtype=jnp.int32)[None, :]
    src_pos = length[:, None] + jnp.clip(path_nodes, 0, block_k - 1)
    dst_pos = length[:, None] + j
    keep = (j < khat[:, None]) & (jnp.clip(path_nodes, 0, block_k - 1) != j)
    new = dict(cache)
    if "kp" in cache:
        kp, vp, tbl = cache["kp"], cache["vp"], cache["tbl"]
        num_pages, ps, kvh, hd = kp.shape
        rows = jnp.arange(b)[:, None]
        phys_src = tbl[rows, src_pos // ps] * ps + src_pos % ps
        phys_dst = tbl[rows, dst_pos // ps] * ps + dst_pos % ps
        kf = kp.reshape(num_pages * ps, kvh, hd)
        vf = vp.reshape(num_pages * ps, kvh, hd)
        m = keep.reshape(-1)[:, None, None]
        kvals = jnp.where(m, kf[phys_src.reshape(-1)], kf[phys_dst.reshape(-1)])
        vvals = jnp.where(m, vf[phys_src.reshape(-1)], vf[phys_dst.reshape(-1)])
        new["kp"] = kf.at[phys_dst.reshape(-1)].set(kvals).reshape(kp.shape)
        new["vp"] = vf.at[phys_dst.reshape(-1)].set(vvals).reshape(vp.shape)
        return new
    buf_len = cache["k"].shape[1]
    nres = _reserved_slots(cfg, layer_idx, buf_len)
    sslot = _slot_for(src_pos, buf_len, nres)
    dslot = _slot_for(dst_pos, buf_len, nres)

    def row(buf, ss, ds, m):
        vals = jnp.where(m[:, None, None], buf[ss], buf[ds])
        return buf.at[ds].set(vals)

    new["k"] = jax.vmap(row)(cache["k"], sslot, dslot, keep)
    new["v"] = jax.vmap(row)(cache["v"], sslot, dslot, keep)
    return new


# ---------------------------------------------------------------------------
# Cross attention (paper's encoder-decoder MT setting)
# ---------------------------------------------------------------------------


def cross_attn_init(key, cfg: ModelConfig, *, dtype=jnp.float32) -> Dict:
    return attn_init(key, cfg, dtype=dtype, cross=True)


def cross_attn_apply(p, cfg: ModelConfig, x, enc_kv, enc_mask=None):
    """x: (B, Sq, d); enc_kv: (k, v) each (B, Se, KV, hd) precomputed."""
    b, sq, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    if "q_norm" in p:
        q = norm_apply(p["q_norm"], q)
    k, v = enc_kv
    se = k.shape[1]
    if enc_mask is None:
        mask = jnp.ones((1, sq, se), bool)
    else:
        mask = enc_mask[:, None, :]
    ctx = _gqa_attend(q, k, v, mask, head_dim=cfg.resolved_head_dim)
    return _out_proj(p, ctx)


def cross_kv(p, cfg: ModelConfig, enc_out):
    """Precompute encoder K/V once per sequence (no RoPE across modalities)."""
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"].astype(enc_out.dtype))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"].astype(enc_out.dtype))
    if "k_norm" in p:
        k = norm_apply(p["k_norm"], k)
    return k, v
