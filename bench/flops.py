"""Operations and bytes that the work requires, from the configuration's
shapes alone.

They count what a decoding step has to do, not what the program happens to
compute, so a later change that stops computing something needless, or
adds a kernel, is measured against the same yardstick:

* a verify step of B rows, block k and K heads: all weights read once, the
  KV of each row's context read once; B·k positions through the trunk and
  attention over each row's context; B·(K-1) positions through the head
  FFNs (head 1 is the identity); B·(k+K) rows of the vocabulary;
* one greedy decoding step per committed token: the trunk, the
  unembedding and attention over the token's context.

Bytes are of the served dtype (bf16, 2 bytes).
"""
from __future__ import annotations

from typing import Dict, Sequence

from bench.weights import dims

BYTES = 2


def trunk_params_per_layer(m: Dict) -> int:
    d, hd = m["d"], m["hd"]
    attn = d * m["heads"] * hd * 2 + d * m["kv"] * hd * 2
    return attn + 3 * d * m["ff"]


def weight_bytes(c: Dict) -> int:
    m = dims(c)
    heads = 2 * m["d"] * m["k"] * m["dh"]
    return BYTES * (m["layers"] * trunk_params_per_layer(m)
                    + m["vocab"] * m["d"] + heads)


def kv_bytes(c: Dict, context: int) -> int:
    m = dims(c)
    return BYTES * m["layers"] * 2 * m["kv"] * m["hd"] * context


def attention_flops(m: Dict, queries: int, context: int) -> int:
    """Scores and weighted values of ``queries`` positions over
    ``context`` keys, all layers."""
    return 4 * m["layers"] * queries * context * m["heads"] * m["hd"]


def verify_step(c: Dict, contexts: Sequence[int]) -> Dict:
    """FLOPs and bytes one verify step of ``len(contexts)`` active rows
    requires."""
    m = dims(c)
    b, k = len(contexts), m["k"]
    flops = 2 * b * k * m["layers"] * trunk_params_per_layer(m)
    flops += sum(attention_flops(m, k, ctx + k) for ctx in contexts)
    flops += 2 * b * (k - 1) * 2 * m["d"] * m["dh"]
    flops += 2 * b * (k + k) * m["d"] * m["vocab"]
    nbytes = weight_bytes(c) + sum(kv_bytes(c, ctx) for ctx in contexts)
    return {"flops": flops, "bytes": nbytes}


def least_seconds(work: Dict, peaks: Dict) -> float:
    return max(work["flops"] / peaks["bf16_flop_per_s"],
               work["bytes"] / peaks["hbm_bytes_per_s"])


def greedy_flops_per_token(c: Dict, context: float) -> float:
    m = dims(c)
    return (2 * m["layers"] * trunk_params_per_layer(m)
            + 2 * m["d"] * m["vocab"]
            + attention_flops(m, 1, context))
