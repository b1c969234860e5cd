"""Plain reference forward of a granite-style decoder, in float32.

Written from the published architecture (Hugging Face ``GraniteForCausalLM``)
and the configuration file alone; it imports nothing of the program.  One
sequence at a time, one layer at a time, every matrix product at
``Precision.HIGHEST``, so that it fits beside the served weights:

    h  = embed[tokens] * embedding_multiplier
    h += residual_multiplier * attn(rmsnorm(h))      (GQA, RoPE, causal,
                                                     scores * attention_multiplier)
    h += residual_multiplier * down(silu(gate(x)) * up(x)),  x = rmsnorm(h)
    logits = rmsnorm(h) @ embed[:vocab].T / logits_scaling

``fp8=True`` is the control: the same forward with both operands of every
weight product rounded to float8 e4m3 (a per-tensor scale), the precision
one step below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
BUCKET = 256            # sequences are padded to a multiple of this


class Consts(NamedTuple):
    heads: int
    kv: int
    hd: int
    eps: float
    theta: float
    attention_multiplier: float
    residual_multiplier: float
    embedding_multiplier: float
    logits_scaling: float
    vocab: int


def consts(c: Dict) -> Consts:
    h = c["num_attention_heads"]
    return Consts(heads=h, kv=c["num_key_value_heads"],
                  hd=c.get("head_dim") or c["hidden_size"] // h,
                  eps=float(c["rms_norm_eps"]),
                  theta=float(c["rope_theta"]),
                  attention_multiplier=float(c["attention_multiplier"]),
                  residual_multiplier=float(c["residual_multiplier"]),
                  embedding_multiplier=float(c["embedding_multiplier"]),
                  logits_scaling=float(c["logits_scaling"]),
                  vocab=int(c["vocab_size"]))


def _fp8(x):
    scale = jnp.max(jnp.abs(x)) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(eq, x, w, fp8):
    x, w = x.astype(F32), w.astype(F32)
    if fp8:
        x, w = _fp8(x), _fp8(w)
    return jnp.einsum(eq, x, w, precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, theta):
    """x: (S, heads, hd); the half-split rotation of HF Llama/Granite."""
    s, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("k", "fp8"))
def layer(bp, h, *, k: Consts, fp8: bool):
    """One decoder layer over one sequence h: (S, d) float32."""
    s = h.shape[0]
    x = _rmsnorm(h, bp["ln1"]["scale"], k.eps)
    q = _rope(_mm("sd,dhk->shk", x, bp["attn"]["wq"], fp8), k.theta)
    kk = _rope(_mm("sd,dhk->shk", x, bp["attn"]["wk"], fp8), k.theta)
    v = _mm("sd,dhk->shk", x, bp["attn"]["wv"], fp8)
    g = k.heads // k.kv
    qg = q.reshape(s, k.kv, g, k.hd)
    scores = jnp.einsum("skgd,tkd->kgst", qg, kk, precision=HIGHEST)
    scores = scores * k.attention_multiplier
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("kgst,tkd->skgd", probs, v,
                     precision=HIGHEST).reshape(s, k.heads, k.hd)
    h = h + k.residual_multiplier * _mm("shk,hkd->sd", ctx,
                                        bp["attn"]["wo"], fp8)
    x = _rmsnorm(h, bp["ln2"]["scale"], k.eps)
    gate = _mm("sd,df->sf", x, bp["mlp"]["w1"]["w"], fp8)
    up = _mm("sd,df->sf", x, bp["mlp"]["w3"]["w"], fp8)
    y = _mm("sf,fd->sd", jax.nn.silu(gate) * up, bp["mlp"]["w2"]["w"], fp8)
    return h + k.residual_multiplier * y


@functools.partial(jax.jit, static_argnames=("k",))
def embed(table, tokens, *, k: Consts):
    return table[tokens].astype(F32) * k.embedding_multiplier


@functools.partial(jax.jit, static_argnames=("k", "fp8"))
def head_logits(final_scale, table, h, *, k: Consts, fp8: bool):
    """Logits over the real vocabulary for rows h: (S, d)."""
    x = _rmsnorm(h, final_scale, k.eps)
    return _mm("sd,vd->sv", x, table[:k.vocab], fp8) / k.logits_scaling


def bucketed(n: int) -> int:
    return -(-n // BUCKET) * BUCKET


def hidden_states(params: Dict, c: Dict, seqs: List[List[int]], *,
                  length: int, fp8: bool = False) -> List[jnp.ndarray]:
    """Last hidden state of each sequence, zero-padded to ``length``
    rounded up to ``BUCKET`` (one shape for every sequence of a cell;
    causal, so the padding never reaches a real position), layer by
    layer."""
    k = consts(c)
    n = bucketed(length)
    rows = []
    for s in seqs:
        row = np.zeros((n,), np.int32)
        row[:len(s)] = s
        rows.append(row)
    hs = [embed(params["embed"]["table"], jnp.asarray(r), k=k) for r in rows]
    for bp in params["blocks"]:
        hs = [layer(bp, h, k=k, fp8=fp8) for h in hs]
    return hs
