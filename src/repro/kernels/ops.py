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
from jax.experimental.pallas.ops.tpu.megablox import gmm
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


def _gmm_tiling(m: int, k: int, n: int):
    """(tm, tk, tn) for ``gmm``: row tiles of 128 (m is a multiple, or
    less), weight blocks of up to 512 × 2048; the fastest of six tilings
    at DeepSeek-V2-Lite's widths on a TPU v5e (PERF.md §6)."""
    return min(m, 128), min(k, 512), min(n, 2048)


def grouped_matmul(lhs, rhs, group_sizes):
    """lhs (m, k), rows sorted by group; rhs (G, k, n); group_sizes (G,)
    int32 summing to m -> (m, n) in lhs's dtype: each row times its own
    group's rhs, accumulated in float32 (megablox ``gmm``).  Each group's
    weights are read once per row tile it touches; empty groups cost
    nothing.  The kernel runs interpreted off the TPU, chosen by the
    platform the program is lowered for, so an ahead-of-time compile for
    a TPU gets the kernel itself."""
    m = lhs.shape[0]
    tm = min(128, -(-m // 16) * 16)     # rows per tile: 16k, at most 128
    lhs = jnp.pad(lhs, ((0, -(-m // tm) * tm - m), (0, 0)))
    rhs = rhs.astype(lhs.dtype)

    def product(lhs, rhs, group_sizes, offset):
        def run(interpret):
            return functools.partial(
                gmm, preferred_element_type=lhs.dtype, tiling=_gmm_tiling,
                group_offset=offset, interpret=interpret)
        return jax.lax.platform_dependent(lhs, rhs, group_sizes,
                                          tpu=run(False), default=run(True))

    mesh = active_mesh()
    if mesh is None:
        return product(lhs, rhs, group_sizes, None)[:m]
    # GSPMD cannot partition a Mosaic kernel: each model shard multiplies
    # the rows of its own groups (gmm zeroes the others) and the shards'
    # outputs are summed
    ax = "model" if ("model" in mesh.axis_names
                     and rhs.shape[0] % mesh.shape["model"] == 0) else None
    local = rhs.shape[0] // (mesh.shape["model"] if ax else 1)

    def shard(lhs, rhs, group_sizes):
        if ax is None:
            return product(lhs, rhs, group_sizes, None)
        offset = (jax.lax.axis_index(ax) * local).astype(jnp.int32)
        return jax.lax.psum(product(lhs, rhs, group_sizes, offset), ax)

    out = jax.shard_map(shard, mesh=mesh, in_specs=(P(), P(ax), P()),
                        out_specs=P(), check_vma=False)(lhs, rhs, group_sizes)
    return out[:m]
