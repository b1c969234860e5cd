"""Jit'd public wrappers for the Pallas kernels.

On CPU (this container) the kernels execute in Pallas interpret mode, which
runs the kernel body in Python/XLA per grid step — correct but slow, so the
model stack uses the jnp paths by default and the kernels are exercised by
tests/benchmarks and on real TPU backends (``use_kernels=True``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.block_attention import (
    tree_verify_attention_pallas,
    verify_attention_pallas,
)
from repro.kernels.fused_heads import fused_heads_topk_pallas
from repro.kernels.fused_verify import fused_verify_pallas
from repro.kernels.paged_attention import paged_verify_attention_pallas
from repro.kernels.rwkv6_scan import rwkv6_scan_pallas
from repro.sharding.policy import active_mesh, batch_axes


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("window", "num_meta", "block_kv",
                                             "interpret"))
def verify_attention(q, k, v, q_pos, kv_pos, *, window: int = 0,
                     num_meta: int = 0, block_kv: int = 512,
                     interpret: bool | None = None):
    """BPD verify-substep attention (see kernels.block_attention)."""
    interp = (not on_tpu()) if interpret is None else interpret
    return verify_attention_pallas(q, k, v, q_pos, kv_pos, window=window,
                                   num_meta=num_meta, block_kv=block_kv,
                                   interpret=interp)


@functools.partial(jax.jit, static_argnames=("window", "num_meta", "block_kv",
                                             "interpret"))
def tree_verify_attention(q, k, v, q_pos, kv_pos, kv_node, anc_bits, *,
                          window: int = 0, num_meta: int = 0,
                          block_kv: int = 512, interpret: bool | None = None):
    """Tree-verification attention: score a whole candidate tree in one
    forward (see kernels.block_attention / kernels.tree_mask)."""
    interp = (not on_tpu()) if interpret is None else interpret
    return tree_verify_attention_pallas(q, k, v, q_pos, kv_pos, kv_node,
                                        anc_bits, window=window,
                                        num_meta=num_meta, block_kv=block_kv,
                                        interpret=interp)


@functools.partial(jax.jit, static_argnames=("window", "num_meta",
                                             "interpret"))
def paged_verify_attention(q, kp, vp, tbl, q_pos, kv_pos, *, window: int = 0,
                           num_meta: int = 0, interpret: bool | None = None):
    """BPD verify attention over a paged KV pool (see kernels.paged_attention)."""
    interp = (not on_tpu()) if interpret is None else interpret
    return paged_verify_attention_pallas(q, kp, vp, tbl, q_pos, kv_pos,
                                         window=window, num_meta=num_meta,
                                         interpret=interp)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan(r, k, v, logw, u, *, chunk: int = 16,
               interpret: bool | None = None):
    """Chunked RWKV-6 wkv scan (see kernels.rwkv6_scan)."""
    interp = (not on_tpu()) if interpret is None else interpret
    return rwkv6_scan_pallas(r, k, v, logw, u, chunk=chunk, interpret=interp)


@functools.partial(jax.jit, static_argnames=("criterion", "top_k", "epsilon",
                                             "block_rows", "block_v",
                                             "interpret"))
def fused_verify(p1_logits, proposals, *, criterion: str, top_k: int = 1,
                 epsilon: float = 0.0, block_rows: int = 64,
                 block_v: int = 1024, interpret: bool | None = None):
    """One-pass block verification: streaming top-T + criterion compare +
    prefix-accept scan (see kernels.fused_verify).  Returns (accepts (B, k)
    bool, k̂ (B,) int32, accepted_tokens (B, k), next_greedy (B,))."""
    interp = (not on_tpu()) if interpret is None else interpret
    if p1_logits.shape[1] == 1:                  # degenerate 1-slot block:
        from repro.kernels import ref            # nothing to scan — oracle
        return ref.fused_verify(p1_logits, proposals, criterion=criterion,
                                top_k=top_k, epsilon=epsilon)
    run = functools.partial(fused_verify_pallas, criterion=criterion,
                            top_k=top_k, epsilon=float(epsilon),
                            block_rows=block_rows, block_v=block_v,
                            interpret=interp)
    mesh = active_mesh()
    if mesh is not None:
        # GSPMD cannot partition a Mosaic kernel (it would replicate it
        # behind an implicit all-gather): run it per batch shard, with the
        # model-sharded vocab gathered explicitly
        ax = batch_axes(mesh, p1_logits.shape[0])
        run = jax.shard_map(
            run, mesh=mesh, in_specs=(P(ax, None, None), P(ax, None)),
            out_specs=(P(ax, None), P(ax), P(ax, None), P(ax)),
            check_vma=False)
    return run(p1_logits, proposals)


@functools.partial(jax.jit, static_argnames=("vocab", "top_t", "block_rows",
                                             "block_v", "interpret"))
def fused_heads_topk(o, w_vocab, *, vocab: int, top_t: int = 4,
                     block_rows: int = 256, block_v: int = 1024,
                     interpret: bool | None = None):
    """Streaming head-logits top-T (see kernels.fused_heads)."""
    interp = (not on_tpu()) if interpret is None else interpret
    return fused_heads_topk_pallas(o, w_vocab, vocab=vocab, top_t=top_t,
                                   block_rows=block_rows, block_v=block_v,
                                   interpret=interp)
