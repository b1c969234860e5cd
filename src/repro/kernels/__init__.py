"""Pallas TPU kernels for the BPD serving hot spots (+ pure-jnp oracles).

  * ``block_attention``  — k-query verify attention vs a long KV cache,
                           plus the tree-verification masking variant
  * ``paged_attention``  — same verify substep over a paged KV pool
                           (block-table gather via scalar prefetch)
  * ``rwkv6_scan``       — chunked RWKV-6 wkv linear-attention scan
  * ``fused_heads``      — streaming head-logits top-T (no k×V materialization)
  * ``fused_verify``     — one-pass accept: streaming top-T + criterion
                           compare + prefix-accept scan
  * ``tree_mask``        — candidate-tree topologies (ancestor masks,
                           packed bitmasks) for tree verification
  * ``ops.grouped_matmul`` — rows sorted by group times their group's
                           weights (JAX's megablox ``gmm``): the MoE's
                           no-drop expert products

``ops`` holds the jit'd wrappers (interpret mode on CPU); ``ref`` the
oracles used by the per-kernel shape/dtype sweep tests.
"""
from repro.kernels import ops, ref  # noqa: F401
from repro.kernels.ops import (  # noqa: F401
    fused_heads_topk,
    fused_verify,
    grouped_matmul,
    paged_verify_attention,
    rwkv6_scan,
    tree_verify_attention,
    verify_attention,
)
from repro.kernels.tree_mask import TreeTopology, default_tree  # noqa: F401
