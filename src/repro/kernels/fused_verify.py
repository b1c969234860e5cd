"""Pallas TPU kernel: fused one-pass block verification (paper §3, §5.1–5.2).

The BPD accept step is a chain of vocab-dimension ops on the verify
forward's p_1 logits — argmax / top-k per block slot, a compare against the
drafted tokens, and the longest-accepted-prefix scan.  Run separately,
each op round-trips the (B, k, V) logit tensor through HBM (V reaches 256k
padded for the assigned archs).  This kernel streams the logits once in
``block_v`` vocab tiles, keeps a running top-T (values, ids) carry per
(row, slot) in VMEM, and on the last tile applies the criterion to each
(row, slot): slot i's top-T against proposal i+1.  The tiny (B, k)
longest-prefix scan then runs in the wrapper, which returns per row:

    accepts (B, k) — per-slot acceptance (column 0 always True, k̂ ≥ 1)
    k̂      (B,)   — longest accepted prefix (before schedule clamping)
    tokens  (B, k) — the accepted prefix of the draft, zero beyond k̂
    next    (B,)   — the verifier's greedy token at slot k̂-1 (the one
                     guaranteed-correct token every iteration commits)

Criterion variants are compile-time (``functools.partial``): ``exact``
(§3 greedy match), ``topk`` (§5.1, T = top_k carry), ``distance`` (§5.2
ordinal tolerance).  The Mosaic TPU compiler has no ``top_k``, so the
carry∪tile merge is T rounds of max → lowest-index argmax → mask.
Tie-breaking matches ``lax.top_k`` / ``jnp.argmax`` exactly: within a
tile the lowest lane wins, and the carry (earlier, lower ids) wins ties
against the tile, so equal logits resolve to the lowest token id in both
the fused and unfused paths.

Grid: (num_row_tiles, num_vocab_tiles); vocab axis sequential, carry in
VMEM.  Row tiles hold whole batch rows (rn = rb·k, a multiple of 8).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
CRITERIA = ("exact", "topk", "distance")


def _fused_verify_kernel(logits_ref, cand_ref,              # inputs
                         ok_ref, greedy_ref,                # outputs
                         bval_ref, bidx_ref,                # scratch
                         *, criterion: str, top_t: int, block_v: int,
                         vocab: int, epsilon: float):
    vb = pl.program_id(1)

    @pl.when(vb == 0)
    def _init():
        bval_ref[...] = jnp.full_like(bval_ref, -jnp.inf)
        bidx_ref[...] = jnp.zeros_like(bidx_ref)

    x = logits_ref[...].astype(jnp.float32)                # (rn, block_v)
    base = vb * block_v
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    x = jnp.where(lane + base < vocab, x, NEG_INF)         # mask vocab pad

    # merge the sorted carry with this tile: T rounds of max, then the
    # lowest-index argmax, then mask.  The carry holds earlier (lower) ids,
    # so it wins ties — the order lax.top_k / jnp.argmax give.
    cval, cidx = bval_ref[...], bidx_ref[...]              # (rn, T)
    col = jax.lax.broadcasted_iota(jnp.int32, cval.shape, 1)
    taken = jnp.zeros(x.shape, jnp.bool_)
    used = jnp.zeros((x.shape[0], 1), jnp.int32)           # carry entries taken
    new_v = jnp.full_like(cval, -jnp.inf)
    new_i = jnp.zeros_like(cidx)
    for t in range(top_t):
        head = col == used                                 # first unused carry
        cv = jnp.max(jnp.where(head, cval, -jnp.inf), axis=1, keepdims=True)
        ci = jnp.max(jnp.where(head, cidx, -1), axis=1, keepdims=True)
        xm = jnp.where(taken, -jnp.inf, x)
        tv = jnp.max(xm, axis=1, keepdims=True)
        ti = jnp.min(jnp.where(xm == tv, lane, block_v), axis=1,
                     keepdims=True)
        from_carry = (cv > tv) | ((cv == tv) & (cv > -jnp.inf))
        new_v = jnp.where(col == t, jnp.where(from_carry, cv, tv), new_v)
        new_i = jnp.where(col == t, jnp.where(from_carry, ci, ti + base),
                          new_i)
        used = used + from_carry.astype(jnp.int32)
        taken = taken | (~from_carry & (lane == ti))
    bval_ref[...] = new_v
    bidx_ref[...] = new_i

    @pl.when(vb == pl.num_programs(1) - 1)
    def _finish():
        greedy = jnp.max(jnp.where(col == 0, new_i, -1), axis=1,
                         keepdims=True)                    # (rn, 1)
        cand = cand_ref[...]                               # (rn, 1)
        if criterion == "exact":
            ok = cand == greedy
        elif criterion == "topk":
            ok = jnp.max(jnp.where(new_i == cand, 1, 0), axis=1,
                         keepdims=True) > 0
        elif criterion == "distance":
            ok = jnp.abs(cand - greedy).astype(jnp.float32) <= epsilon
        else:  # pragma: no cover - guarded by the wrapper
            raise ValueError(f"unknown criterion {criterion!r}")
        ok_ref[...] = ok.astype(jnp.int32)
        greedy_ref[...] = greedy


def _prefix_accept(ok, greedy, proposals):
    """Longest accepted prefix from per-slot verdicts.

    ok / greedy: (B, k) — slot i's verdict on proposal i+1 and its greedy
    token.  Returns (accepts, k̂, accepted tokens, next greedy)."""
    b, k = proposals.shape
    acc = jnp.concatenate([jnp.ones((b, 1), jnp.bool_), ok[:, :k - 1]],
                          axis=1)
    rej = jnp.logical_not(acc)
    first = jnp.argmax(rej.astype(jnp.int32), axis=1)
    khat = jnp.where(jnp.any(rej, axis=1), first, k).astype(jnp.int32)
    slot = jnp.arange(k)[None, :]
    toks = jnp.where(slot < khat[:, None], proposals, 0).astype(jnp.int32)
    nxt = jnp.take_along_axis(greedy, (khat - 1)[:, None], axis=1)[:, 0]
    return acc, khat, toks, nxt


def fused_verify_pallas(p1_logits, proposals, *, criterion: str,
                        top_k: int = 1, epsilon: float = 0.0,
                        block_rows: int = 64, block_v: int = 1024,
                        interpret: bool = False):
    """p1_logits: (B, k, V) verify-forward p_1 logits at block slots 0..k-1;
    proposals: (B, k) int32 draft tokens (slot 0 = the verified token).

    Returns (accepts (B, k) bool, k̂ (B,) int32, accepted_tokens (B, k)
    int32, next_greedy (B,) int32).  Bit-identical to ``ref.fused_verify``
    and to the unfused ``Acceptor`` path for the same criterion.
    """
    if criterion not in CRITERIA:
        raise ValueError(f"unknown criterion {criterion!r}; one of {CRITERIA}")
    b, k, v = p1_logits.shape
    top_t = max(1, int(top_k)) if criterion == "topk" else 1
    block_v = min(block_v, max(128, v))
    vp = ((v + block_v - 1) // block_v) * block_v

    # whole batch rows per tile, rn = rb·k aligned to the 8-sublane tile
    rn_unit = (k * 8) // math.gcd(k, 8)
    rb = (rn_unit // k) * max(1, block_rows // rn_unit)
    b_pad = ((b + rb - 1) // rb) * rb
    rn = rb * k

    lg = jnp.pad(p1_logits.astype(jnp.float32),
                 ((0, b_pad - b), (0, 0), (0, vp - v)),
                 constant_values=NEG_INF).reshape(b_pad * k, vp)
    props = proposals.astype(jnp.int32)
    # row (b, i) checks proposal i+1; the last slot's verdict is unused
    cand = jnp.pad(props[:, 1:], ((0, b_pad - b), (0, 1))).reshape(-1, 1)

    grid = (b_pad // rb, vp // block_v)
    ok, greedy = pl.pallas_call(
        functools.partial(_fused_verify_kernel, criterion=criterion,
                          top_t=top_t, block_v=block_v, vocab=v,
                          epsilon=float(epsilon)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rn, block_v), lambda ri, vi: (ri, vi)),
            pl.BlockSpec((rn, 1), lambda ri, vi: (ri, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rn, 1), lambda ri, vi: (ri, 0)),
            pl.BlockSpec((rn, 1), lambda ri, vi: (ri, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b_pad * k, 1), jnp.int32),
            jax.ShapeDtypeStruct((b_pad * k, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((rn, top_t), jnp.float32),
            pltpu.VMEM((rn, top_t), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lg, cand)
    ok = ok[:b * k, 0].reshape(b, k) != 0
    greedy = greedy[:b * k, 0].reshape(b, k)
    return _prefix_accept(ok, greedy, props)
