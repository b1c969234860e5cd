"""GQA attention with RoPE, sliding windows, and BPD-aware KV caching, and
DeepSeek-V2's multi-head latent attention (MLA, ``cfg.kv_lora_rank > 0``).

Three entry points:
  * ``attn_full``    — parallel forward over a whole sequence (training /
                       prefill / encoder).  Optionally returns post-RoPE K/V
                       so prefill can populate the cache.
  * ``attn_cached``  — scores a block of ``k`` fresh tokens against the KV
                       cache *and* each other (the paper's verify substep).
  * ``cross_attn``   — encoder-decoder cross attention (paper's MT setting).

Masking is computed from absolute positions so the blockwise-parallel-decode
rollback ("length decreases by up to k-1") needs no data movement.

MLA (arXiv:2405.04434 §2.1; HF ``modeling_deepseek.py``) takes the same
entry points: a model whose config has ``kv_lora_rank > 0`` attends through
``mla_full`` (prefill, the expanded form) and ``mla_cached`` (the verify
step, the absorbed form), and caches one normalised latent and one shared
RoPE key per token (``models/cache.py``'s latent layout).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.layers import (
    apply_rope,
    apply_rope_pairs,
    dense_init,
    norm_apply,
    norm_init,
    rope_freqs,
    yarn_inv_freq,
    yarn_mscale,
)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def attn_init(key, cfg: ModelConfig, *, dtype=jnp.float32, cross: bool = False) -> Dict:
    if cfg.is_mla and not cross:
        return mla_init(key, cfg, dtype=dtype)
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
    if cfg.is_mla:
        if bidirectional or kv_chunk:
            raise NotImplementedError(
                "latent attention (MLA) runs causal and unchunked only")
        y, latent = mla_full(p, cfg, x, positions)
        return (y, latent) if return_kv else y
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
    A latent (MLA) cache takes the latent as ``k`` and the RoPE key as ``v``.
    """
    if "ckv" in cache:
        return latent_cache_write(cache, k, v, positions)
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
    if cfg.is_mla:
        return mla_cached(p, cfg, x_block, cache, length, kv_chunk=kv_chunk,
                          tree=tree)
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
# Multi-head latent attention (DeepSeek-V2)
# ---------------------------------------------------------------------------
#
#   q          = x Wq                              (H, nope + rope), no q LoRA
#   [c, k_pe]  = x Wkv_a;  c = RMSNorm(c)          (kv_lora_rank), (rope)
#   k_nope     = c Wkb,  v = c Wvb                 (H, nope), (H, v)
#   score_h    = (q_nope·k_nope + q_pe·k_pe) · scale
#   y          = softmax(score) v Wo
#
# q_pe and k_pe are RoPE'd at their positions, k_pe once for every head.
# ``wkb`` / ``wvb`` are HF's ``kv_b_proj`` split into its key and value
# halves.  The cache holds c and the RoPE'd k_pe.  The verify step uses the
# absorbed form: q_nope Wkb^T is scored against the cached latents, and Wvb
# is applied after the weighted sum of latents, so no cached latent is ever
# expanded to per-head keys and values.


def mla_init(key, cfg: ModelConfig, *, dtype=jnp.float32) -> Dict:
    d, h, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 5)
    return {
        "wq": dense_init(ks[0], d, h * (nope + rope), dtype=dtype)["w"]
        .reshape(d, h, nope + rope),
        "wkv_a": dense_init(ks[1], d, r + rope, dtype=dtype)["w"],
        "kv_norm": norm_init(r, kind="rmsnorm", dtype=dtype),
        "wkb": dense_init(ks[2], r, h * nope, dtype=dtype)["w"]
        .reshape(r, h, nope),
        "wvb": dense_init(ks[3], r, h * vd, dtype=dtype)["w"].reshape(r, h, vd),
        "wo": dense_init(ks[4], h * vd, d, dtype=dtype)["w"].reshape(h, vd, d),
    }


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """(nope + rope)^-1/2, times YaRN's mscale(mscale_all_dim) squared."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    ys = cfg.rope_scaling
    if ys is not None and ys.mscale_all_dim:
        scale *= yarn_mscale(ys.factor, ys.mscale_all_dim) ** 2
    return scale


def _mla_rope(cfg: ModelConfig):
    """(inverse frequencies, cos/sin scale) of the decoupled RoPE dims."""
    dim, ys = cfg.qk_rope_head_dim, cfg.rope_scaling
    if ys is None:
        return rope_freqs(dim, cfg.rope_theta), 1.0
    return (yarn_inv_freq(dim, cfg.rope_theta, ys),
            yarn_mscale(ys.factor, ys.mscale)
            / yarn_mscale(ys.factor, ys.mscale_all_dim))


def _mla_project(p, cfg: ModelConfig, x, positions):
    """x: (B, S, d) -> q_nope (B,S,H,nope), q_pe (B,S,H,rope), the normed
    latent c (B,S,r) and k_pe (B,S,rope); q_pe and k_pe RoPE'd."""
    if cfg.sliding_window:
        raise NotImplementedError(
            "latent attention (MLA) has no sliding-window form")
    nope, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    freqs, mscale = _mla_rope(cfg)
    with jax.named_scope("mla.project"):
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
        kv = x @ p["wkv_a"].astype(x.dtype)
        c = norm_apply(p["kv_norm"], kv[..., :r])
        q_pe = apply_rope_pairs(q[..., nope:], positions, freqs, mscale)
        k_pe = apply_rope_pairs(kv[..., None, r:], positions, freqs,
                                mscale)[..., 0, :]
    return q[..., :nope], q_pe, c, k_pe


def _mla_out(p, ctx):
    with jax.named_scope("mla.out"):
        return jnp.einsum("bshk,hkd->bsd", ctx, p["wo"].astype(ctx.dtype))


def mla_full(p, cfg: ModelConfig, x, positions):
    """Causal MLA over a whole sequence in the expanded form (prefill /
    training).  Returns (y, (latent, k_pe)) — what the cache holds."""
    q_nope, q_pe, c, k_pe = _mla_project(p, cfg, x, positions)
    with jax.named_scope("mla.attend"):
        k_nope = jnp.einsum("bsc,chk->bshk", c, p["wkb"].astype(c.dtype))
        v = jnp.einsum("bsc,chk->bshk", c, p["wvb"].astype(c.dtype))
        scores = (jnp.einsum("bqhk,bshk->bhqs", q_nope, k_nope)
                  + jnp.einsum("bqhk,bsk->bhqs", q_pe, k_pe)
                  ).astype(jnp.float32) * mla_softmax_scale(cfg)
        mask = make_causal_mask(positions, positions)
        if mask.ndim == 2:
            mask = mask[None]
        scores = jnp.where(mask[:, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        ctx = jnp.einsum("bhqs,bshk->bqhk", probs, v)
    return _mla_out(p, ctx), (c, k_pe)


def mla_cached(p, cfg: ModelConfig, x_block, cache: Dict, length, *,
               kv_chunk: int = 0, tree=None) -> Tuple[jnp.ndarray, Dict]:
    """The verify step's MLA in the absorbed form: ``k`` fresh tokens at
    positions length .. length+k-1 against the latent cache and each other
    (the same contract as ``attn_cached``)."""
    if tree is not None:
        raise NotImplementedError(
            "tree verification is not implemented for latent attention "
            "(MLA): its cache has no tree-commit path")
    if kv_chunk:
        raise NotImplementedError(
            "latent attention (MLA) has no chunked (kv_chunk) form")
    b, kblk, _ = x_block.shape
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (b,))
    positions = length[:, None] + jnp.arange(kblk, dtype=jnp.int32)[None, :]
    q_nope, q_pe, c, k_pe = _mla_project(p, cfg, x_block, positions)
    cache = latent_cache_write(cache, c, k_pe, positions)
    kv_pos = cache["pos"]
    kv_pos = jnp.where(kv_pos < (length + kblk)[:, None], kv_pos, -1)
    mask = make_causal_mask(positions, kv_pos)                     # (B, k, L)
    ckv, kpe = cache["ckv"], cache["kpe"]
    with jax.named_scope("mla.absorb"):
        q_lat = jnp.einsum("bqhk,chk->bqhc", q_nope,
                           p["wkb"].astype(q_nope.dtype))          # (B,k,H,r)
    with jax.named_scope("mla.attend"):
        scores = (jnp.einsum("bqhc,bsc->bhqs", q_lat, ckv.astype(q_lat.dtype))
                  + jnp.einsum("bqhk,bsk->bhqs", q_pe, kpe.astype(q_pe.dtype))
                  ).astype(jnp.float32) * mla_softmax_scale(cfg)
        scores = jnp.where(mask[:, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(q_lat.dtype)
        ctx_lat = jnp.einsum("bhqs,bsc->bqhc", probs, ckv.astype(probs.dtype))
    with jax.named_scope("mla.absorb"):
        ctx = jnp.einsum("bqhc,chk->bqhk", ctx_lat,
                         p["wvb"].astype(ctx_lat.dtype))
    return _mla_out(p, ctx), cache


def latent_cache_write(cache: Dict, c, k_pe, positions) -> Dict:
    """Write latents ``c`` (B,S,r) and RoPE keys ``k_pe`` (B,S,rope) at
    ``positions`` ((S,) shared or (B, S) per row) into a latent cache; full
    attention, so a position's slot is the position itself."""
    b = cache["ckv"].shape[0]
    if positions.ndim == 1:
        positions = jnp.broadcast_to(positions[None, :],
                                     (b, positions.shape[0]))
    slots = positions.astype(jnp.int32)

    def row_write(buf, slot, val):
        return buf.at[slot].set(val)

    new = dict(cache)
    new["ckv"] = jax.vmap(row_write)(cache["ckv"], slots,
                                     c.astype(cache["ckv"].dtype))
    new["kpe"] = jax.vmap(row_write)(cache["kpe"], slots,
                                     k_pe.astype(cache["kpe"].dtype))
    new["pos"] = jax.vmap(row_write)(cache["pos"], slots, slots)
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
