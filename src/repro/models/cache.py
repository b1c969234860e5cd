"""Decode-time caches: dense slabs and paged pools behind one backend API.

All caches are plain dict pytrees so they thread through ``jax.lax.while_loop``
and ``pjit`` unchanged.

Dense KV layout (per attention layer, ``cache_backend="dense"``):
    k, v : (batch, buf_len, kv_heads, head_dim)   post-RoPE keys
    pos  : (batch, buf_len) int32                 absolute position held by
                                                  slot (-1 = never written)

Dense latent layout (per MLA layer, ``cfg.kv_lora_rank > 0``; dense only):
    ckv  : (batch, buf_len, kv_lora_rank)      normalised KV latent
    kpe  : (batch, buf_len, qk_rope_head_dim)  post-RoPE key shared by heads
    pos  : (batch, buf_len) int32              as above

Paged KV layout (per full-attention layer, ``cache_backend="paged"``):
    kp, vp : (num_pages, page_size, kv_heads, head_dim)  shared page pool
    tbl    : (batch, P) int32       per-row block table: logical page i of
                                    row b lives in physical page tbl[b, i].
                                    Physical page 0 is a permanent trash
                                    page — unmapped entries point at it, so
                                    stray writes land somewhere harmless.
    pos    : (batch, P * page_size) int32   absolute positions, as dense

The *model-level* current length (number of accepted tokens) lives outside
the per-layer dicts (one scalar for the whole model).  Masking is computed
from absolute positions, so blockwise-parallel-decoding rollback is simply
"decrease the length": stale slots have ``pos >= length`` and are masked out
until overwritten.  This invariant is backend-independent — under paging a
rollback reclaims stale *speculative* writes by the same position masking
(the pages stay mapped; no copies, no host round-trip), and whole pages are
only returned to the pool on request eviction (``serving/pages.py``).

For full attention, ``buf_len`` covers the whole context (seq_len + block
slack).  For sliding-window attention, ``buf_len = window + block_k`` — the
``+ block_k`` slack guarantees that speculative writes can never clobber a
slot that is still inside the window after a rollback (see DESIGN.md §4).
Window layers keep the dense ring-buffer layout even under the paged
backend: their buffers are already bounded by the window, so paging buys
nothing and would break the ring-wrap slot assignment.

Backend selection: construct a backend with ``get_backend(dec)`` (reads
``DecodeConfig.cache_backend`` / ``page_size``) and pass it down through
``model.init_caches(..., backend=)``.  The legacy free functions
(``attn_cache_init`` etc.) remain the dense building blocks; new call sites
should go through :class:`KVCacheBackend`.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig


def attn_cache_init(batch: int, buf_len: int, kv_heads: int, head_dim: int, dtype) -> Dict:
    return {
        "k": jnp.zeros((batch, buf_len, kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, buf_len, kv_heads, head_dim), dtype),
        # per-row absolute positions: rows advance at different rates under
        # blockwise parallel decoding (per-row accepted block sizes)
        "pos": jnp.full((batch, buf_len), -1, jnp.int32),
    }


def latent_cache_init(batch: int, buf_len: int, rank: int, rope_dim: int,
                      dtype) -> Dict:
    return {
        "ckv": jnp.zeros((batch, buf_len, rank), dtype),
        "kpe": jnp.zeros((batch, buf_len, rope_dim), dtype),
        "pos": jnp.full((batch, buf_len), -1, jnp.int32),
    }


def paged_attn_cache_init(batch: int, pages_per_row: int, page_size: int,
                          num_pages: int, kv_heads: int, head_dim: int,
                          dtype, *, identity_tbl: bool = False) -> Dict:
    """Paged pool + block table for one full-attention layer.

    ``identity_tbl`` maps row b's logical page i to physical page
    ``1 + b * P + i`` — a fixed, allocator-free layout for run-to-completion
    decode paths.  Serving starts all-trash (``tbl = 0``) and maps pages at
    admission via ``serving.pages.PageAllocator``.
    """
    if identity_tbl:
        tbl = (1 + jnp.arange(batch * pages_per_row, dtype=jnp.int32)
               ).reshape(batch, pages_per_row)
    else:
        tbl = jnp.zeros((batch, pages_per_row), jnp.int32)
    return {
        "kp": jnp.zeros((num_pages, page_size, kv_heads, head_dim), dtype),
        "vp": jnp.zeros((num_pages, page_size, kv_heads, head_dim), dtype),
        "tbl": tbl,
        "pos": jnp.full((batch, pages_per_row * page_size), -1, jnp.int32),
    }


def mamba_cache_init(batch: int, d_inner: int, state_dim: int, conv_width: int, dtype) -> Dict:
    return {
        "conv": jnp.zeros((batch, conv_width - 1, d_inner), dtype),
        "h": jnp.zeros((batch, d_inner, state_dim), jnp.float32),
    }


def rwkv_cache_init(batch: int, d_model: int, num_heads: int, head_dim: int, dtype) -> Dict:
    return {
        "shift_tm": jnp.zeros((batch, d_model), dtype),   # time-mix token shift
        "shift_cm": jnp.zeros((batch, d_model), dtype),   # channel-mix token shift
        "state": jnp.zeros((batch, num_heads, head_dim, head_dim), jnp.float32),
    }


def reset_rows(cache: Dict, mask: jnp.ndarray) -> Dict:
    """Invalidate the cache rows selected by ``mask`` ((B,) bool).

    This is the slot-recycling primitive for continuous-batching serving:
    an evicted request's KV slots get ``pos = -1`` (never-written, masked out
    of every attention) and its recurrent states return to zero, so the row
    can host a freshly admitted request.  K/V values themselves are left in
    place — with ``pos = -1`` they are unreachable, and the admit prefill
    overwrites the whole row anyway.  Paged rows additionally drop their
    block table to the trash page (``tbl = 0``) so any in-flight speculative
    write from the retiring step cannot touch pages the host allocator has
    already handed to another slot.
    """
    out = dict(cache)
    if "attn" in cache:
        a = dict(cache["attn"])
        a["pos"] = jnp.where(mask[:, None], -1, a["pos"])
        if "tbl" in a:
            a["tbl"] = jnp.where(mask[:, None], 0, a["tbl"])
        out["attn"] = a
    for key in ("tm", "mamba"):
        if key in cache:
            out[key] = {
                k: jnp.where(mask.reshape((-1,) + (1,) * (v.ndim - 1)),
                             jnp.zeros_like(v), v)
                for k, v in cache[key].items()
            }
    return out


def scatter_row(cache: Dict, row_cache: Dict, slot, *, constraint=None) -> Dict:
    """Write a batch-1 cache (``row_cache``) into row ``slot`` of ``cache``.

    Used by the serving engine to prefill an admitted request into a freed
    slot while the other slots keep decoding.  Leaf structures must match
    (same layers / buffer lengths); ``slot`` may be a traced int32 scalar.

    ``constraint`` — optional pytree of shardings (NamedSharding /
    PartitionSpec) mirroring ``cache``.  Under a mesh the slot-index write
    is a *global* scatter into a batch-sharded buffer; pinning the result
    keeps GSPMD lowering it as a masked local write on the owning data
    shard instead of replicating the whole KV buffer around the scatter.
    """
    out = jax.tree_util.tree_map(
        lambda full, row: jax.lax.dynamic_update_index_in_dim(
            full, row[0].astype(full.dtype), slot, 0),
        cache, row_cache)
    if constraint is not None:
        out = jax.tree_util.tree_map(jax.lax.with_sharding_constraint,
                                     out, constraint)
    return out


def scatter_row_paged(cache: Dict, row_cache: Dict, slot, tbl_row, write_mask,
                      *, constraint=None) -> Dict:
    """Paged admission: install a prefilled batch-1 row into the page pool.

    ``row_cache`` is a *dense* batch-1 layer cache whose attention buffer is
    exactly ``P * page_size`` long (``PagedBackend.row_init``), so logical
    page i of the row is ``row_k[0, i*ps:(i+1)*ps]``.  ``tbl_row`` ((P,)
    int32) is the host allocator's physical mapping for this slot and
    ``write_mask`` ((P,) bool) selects which pages to actually write: False
    entries are copy-on-write prefix hits (their bytes already live in the
    pool from an earlier identical prompt) or unmapped tail pages.  Masked
    pages are redirected to the trash page 0 instead of gathered-and-
    rewritten, so a CoW-shared page is never touched by admission.

    Non-attention cache parts (recurrent states) scatter densely as usual.
    """
    a = cache["attn"]
    r = row_cache["attn"]
    num_pages, ps, kvh, hd = a["kp"].shape
    P = a["tbl"].shape[1]
    tbl_row = jnp.asarray(tbl_row, jnp.int32)
    write_mask = jnp.asarray(write_mask, bool)
    # masked (shared / unmapped) pages write to the trash page, not the pool
    dst = jnp.where(write_mask, tbl_row, 0)
    row_k = r["k"][0].reshape(P, ps, kvh, hd)
    row_v = r["v"][0].reshape(P, ps, kvh, hd)
    new_attn = dict(a)
    new_attn["kp"] = a["kp"].at[dst].set(row_k.astype(a["kp"].dtype))
    new_attn["vp"] = a["vp"].at[dst].set(row_v.astype(a["vp"].dtype))
    new_attn["tbl"] = jax.lax.dynamic_update_index_in_dim(
        a["tbl"], tbl_row, slot, 0)
    new_attn["pos"] = jax.lax.dynamic_update_index_in_dim(
        a["pos"], r["pos"][0].astype(jnp.int32), slot, 0)

    out = dict(cache)
    out["attn"] = new_attn
    for key in cache:
        if key != "attn":
            out[key] = jax.tree_util.tree_map(
                lambda full, row: jax.lax.dynamic_update_index_in_dim(
                    full, row[0].astype(full.dtype), slot, 0),
                cache[key], row_cache[key])
    if constraint is not None:
        out = jax.tree_util.tree_map(jax.lax.with_sharding_constraint,
                                     out, constraint)
    return out


def attn_buf_len(cfg: ModelConfig, layer_idx: int, context_len: int, block_k: int) -> int:
    """Static KV buffer size for one attention layer.

    Rounded up to a multiple of 256 so the buffer's *length* dim can shard
    over the model axis (flash-decoding-style sequence sharding — used when
    kv_heads doesn't divide the axis).  Extra slots hold pos = -1 and are
    masked out, so padding is semantically free."""
    window = cfg.sliding_window
    if window and layer_idx not in cfg.global_attn_layers:
        # meta tokens (hymba) are global: give them dedicated leading slots by
        # folding them into the window budget.
        n = min(context_len + block_k, window + cfg.num_meta_tokens + block_k)
    else:
        n = context_len + block_k
    return ((n + 255) // 256) * 256


def is_paged(layer_cache: Dict) -> bool:
    """True when a per-layer cache dict carries a paged attention part."""
    return "attn" in layer_cache and "kp" in layer_cache["attn"]


def _is_window_layer(cfg: ModelConfig, layer_idx: int) -> bool:
    return bool(cfg.sliding_window) and layer_idx not in cfg.global_attn_layers


# ---------------------------------------------------------------------------
# KVCacheBackend — the one construction/maintenance surface for decode caches
# ---------------------------------------------------------------------------


class KVCacheBackend:
    """Protocol for decode-cache backends.

    A backend owns the *layout* of the per-layer attention caches and every
    whole-model lifecycle operation the decode and serving paths need:

      init(cfg, batch, context_len, block_k, dtype=None)  -> caches
      row_init(cfg, context_len, block_k, dtype=None)     -> batch-1 caches
                    (dense layout, sized so the row scatters into ``init``'s
                    buffers — the admission prefill workspace)
      reset_rows(caches, mask)                            -> caches
      scatter_or_alloc(caches, row_caches, slot, ...)     -> caches
      specs(cfg, caches, mesh, batch_size)                -> PartitionSpecs
      memory_bytes(cfg, batch, context_len, block_k)      -> int

    plus the per-layer hook ``layer_attn_init`` that
    ``blocks.block_cache_init`` dispatches through.  Select one with
    :func:`get_backend`; ``DecodeConfig.cache_backend`` names it.
    """

    name = "abstract"

    # -- per-layer layout hook (called by blocks.block_cache_init) ----------

    def layer_attn_init(self, cfg: ModelConfig, layer_idx: int, batch: int,
                        context_len: int, block_k: int, dtype) -> Dict:
        raise NotImplementedError

    # -- whole-model lifecycle ----------------------------------------------

    def init(self, cfg: ModelConfig, batch: int, context_len: int,
             block_k: int, dtype=None):
        from repro.models import model as model_lib  # cache <- blocks <- model

        return model_lib.init_caches(cfg, batch, context_len, block_k, dtype,
                                     backend=self)

    def row_init(self, cfg: ModelConfig, context_len: int, block_k: int,
                 dtype=None, *, batch: int = 1):
        """Admission-prefill workspace: ``batch`` rows in the dense row
        layout (batch > 1 = a prefill worker's whole packet at once; each
        row is still scattered into a slot individually)."""
        from repro.models import model as model_lib

        return model_lib.init_caches(cfg, batch, context_len, block_k, dtype,
                                     backend=DenseBackend())

    def reset_rows(self, caches, mask):
        return tuple(reset_rows(c, mask) for c in caches)

    def scatter_or_alloc(self, caches, row_caches, slot, *, tbl_row=None,
                         write_mask=None, constraint=None):
        """Install a prefilled batch-1 row: dense rows scatter, paged rows
        additionally bind the allocator's page mapping (``tbl_row`` /
        ``write_mask``, shared across layers — identical tokens at identical
        positions produce one page-id space for the whole model)."""
        if constraint is None:
            constraint = (None,) * len(caches)
        out = []
        for c, rc, cn in zip(caches, row_caches, constraint):
            if is_paged(c):
                out.append(scatter_row_paged(c, rc, slot, tbl_row, write_mask,
                                             constraint=cn))
            else:
                out.append(scatter_row(c, rc, slot, constraint=cn))
        return tuple(out)

    def specs(self, cfg: ModelConfig, caches, mesh, batch_size: int):
        from repro.sharding import policy as shard_policy

        return shard_policy.cache_specs(cfg, caches, mesh, batch_size)

    def memory_bytes(self, cfg: ModelConfig, batch: int, context_len: int,
                     block_k: int, dtype=None) -> int:
        """HBM footprint of ``init``'s buffers (no allocation happens)."""
        shapes = jax.eval_shape(
            lambda: self.init(cfg, batch, context_len, block_k, dtype))
        return sum(int(np.prod(s.shape)) * s.dtype.itemsize
                   for s in jax.tree_util.tree_leaves(shapes))


class DenseBackend(KVCacheBackend):
    """The original layout: one padded ``buf_len`` KV row per batch slot."""

    name = "dense"

    def layer_attn_init(self, cfg: ModelConfig, layer_idx: int, batch: int,
                        context_len: int, block_k: int, dtype) -> Dict:
        buf = attn_buf_len(cfg, layer_idx, context_len, block_k)
        if cfg.is_mla:
            return latent_cache_init(batch, buf, cfg.kv_lora_rank,
                                     cfg.qk_rope_head_dim, dtype)
        return attn_cache_init(batch, buf, cfg.num_kv_heads,
                               cfg.resolved_head_dim, dtype)


def _refuse_latent(cfg: ModelConfig) -> None:
    if cfg.is_mla:
        raise NotImplementedError(
            f"{cfg.name}: the paged cache pages per-head K/V and has no "
            f"latent (MLA) layout; serve it with cache_backend='dense'")


class _PagedRowBackend(DenseBackend):
    """Dense batch-1 rows whose full-attention buffers are exactly
    ``P * page_size`` long, so the admission prefill's output reshapes
    page-aligned into the pool (see ``scatter_row_paged``)."""

    name = "paged_row"

    def __init__(self, page_size: int):
        self.page_size = page_size

    def layer_attn_init(self, cfg, layer_idx, batch, context_len, block_k,
                        dtype):
        _refuse_latent(cfg)
        if _is_window_layer(cfg, layer_idx):
            return super().layer_attn_init(cfg, layer_idx, batch, context_len,
                                           block_k, dtype)
        P = pages_per_row(context_len, block_k, self.page_size)
        return attn_cache_init(batch, P * self.page_size, cfg.num_kv_heads,
                               cfg.resolved_head_dim, dtype)


def pages_per_row(context_len: int, block_k: int, page_size: int) -> int:
    """Block-table width P: pages to address ``context_len + block_k``
    positions (the same span a dense buffer covers, minus the 256-padding)."""
    return -(-(context_len + block_k) // page_size)


class PagedBackend(KVCacheBackend):
    """Paged pool layout for full-attention layers (windowed layers stay
    dense — their ring buffers are already window-bounded).

    ``num_pages = 0`` (the default) auto-sizes the pool to the identity
    worst case ``1 + batch * P`` and lays the block tables out identity —
    run-to-completion decode needs no allocator.  Serving passes an explicit
    pool size (``EngineConfig.page_pool_pages``) with ``managed=True``:
    tables start all-trash and ``serving.pages.PageAllocator`` maps pages at
    admission (with copy-on-write prefix sharing).
    """

    name = "paged"

    def __init__(self, page_size: int = 16, num_pages: int = 0,
                 managed: bool = False):
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        self.managed = bool(managed)

    def layer_attn_init(self, cfg: ModelConfig, layer_idx: int, batch: int,
                        context_len: int, block_k: int, dtype) -> Dict:
        _refuse_latent(cfg)
        if _is_window_layer(cfg, layer_idx):
            buf = attn_buf_len(cfg, layer_idx, context_len, block_k)
            return attn_cache_init(batch, buf, cfg.num_kv_heads,
                                   cfg.resolved_head_dim, dtype)
        P = pages_per_row(context_len, block_k, self.page_size)
        pool = self.num_pages or (1 + batch * P)
        return paged_attn_cache_init(batch, P, self.page_size, pool,
                                     cfg.num_kv_heads, cfg.resolved_head_dim,
                                     dtype, identity_tbl=not self.managed)

    def row_init(self, cfg: ModelConfig, context_len: int, block_k: int,
                 dtype=None, *, batch: int = 1):
        from repro.models import model as model_lib

        return model_lib.init_caches(
            cfg, batch, context_len, block_k, dtype,
            backend=_PagedRowBackend(self.page_size))


def get_backend(dec=None, *, num_pages: int = 0,
                managed: bool = False) -> KVCacheBackend:
    """The blessed backend constructor: reads ``DecodeConfig.cache_backend``
    (+ ``page_size``); serving passes its pool size and ``managed=True``."""
    name = getattr(dec, "cache_backend", "dense") if dec is not None else "dense"
    if name in ("", "dense"):
        return DenseBackend()
    if name == "paged":
        return PagedBackend(getattr(dec, "page_size", 16),
                            num_pages=num_pages, managed=managed)
    raise ValueError(
        f"unknown cache_backend {name!r}: expected 'dense' or 'paged'")
