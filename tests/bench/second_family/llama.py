"""A Llama family (``model_type`` "llama"), for tests only: a second model
that the benchmark takes as new files alone.

Unlike granite it has no μP multipliers and an untied output head
(``lm_head``), so its weights' layout, its reference's head and its byte
count differ from granite's.  The reference, in float32 through
``bench.reference``'s toolkit:

    h  = embed[tokens]
    h += attn(rmsnorm(h))                 (GQA, RoPE, causal, 1/sqrt(hd))
    h += down(silu(gate(x)) * up(x)),  x = rmsnorm(h)
    logits = rmsnorm(h) @ lm_head[:, :vocab]
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec
from bench.reference import F32, HIGHEST, _mm, _rmsnorm, _rope, bucketed

BYTES = 2


def program_config(c: Dict):
    from repro.config import ModelConfig

    if not math.isclose(float(c["rms_norm_eps"]), 1e-6, rel_tol=1e-9):
        raise spec.SpecError("the served model's RMSNorm uses 1e-6")
    cfg = ModelConfig(
        name=c["name"], num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], rope_theta=float(c["rope_theta"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        bpd_k=c["bpd_heads"], bpd_hidden=c["bpd_head_hidden"],
        param_dtype=c["torch_dtype"], dtype=c["compute_dtype"])
    if cfg.padded_vocab_size != c["padded_vocab_size"]:
        raise spec.SpecError("padded vocabulary differs from the program's")
    return cfg


def dims(c: Dict) -> Dict:
    d, h = c["hidden_size"], c["num_attention_heads"]
    return {"d": d, "layers": c["num_hidden_layers"], "heads": h,
            "kv": c["num_key_value_heads"], "hd": d // h,
            "ff": c["intermediate_size"], "vocab": c["vocab_size"],
            "padded_vocab": c["padded_vocab_size"], "k": c["bpd_heads"],
            "dh": c["bpd_head_hidden"]}


def layout(c: Dict):
    m = dims(c)
    d, hd, ff, k, dh = m["d"], m["hd"], m["ff"], m["k"], m["dh"]
    out = [(("embed", "table"), (m["padded_vocab"], d), 0.02),
           (("lm_head", "w"), (d, m["padded_vocab"]), d ** -0.5)]
    for i in range(m["layers"]):
        b = ("blocks", i)
        out += [
            (b + ("ln1", "scale"), (d,), -1.0),
            (b + ("attn", "wq"), (d, m["heads"], hd), d ** -0.5),
            (b + ("attn", "wk"), (d, m["kv"], hd), d ** -0.5),
            (b + ("attn", "wv"), (d, m["kv"], hd), d ** -0.5),
            (b + ("attn", "wo"), (m["heads"], hd, d),
             (m["heads"] * hd) ** -0.5),
            (b + ("ln2", "scale"), (d,), -1.0),
            (b + ("mlp", "w1", "w"), (d, ff), d ** -0.5),
            (b + ("mlp", "w3", "w"), (d, ff), d ** -0.5),
            (b + ("mlp", "w2", "w"), (ff, d), ff ** -0.5),
        ]
    out += [(("final_norm", "scale"), (d,), -1.0),
            (("bpd_heads", "w1"), (d, k, dh), d ** -0.5),
            (("bpd_heads", "b1"), (k, dh), 0.0),
            (("bpd_heads", "w2"), (k, dh, d), 0.1 * dh ** -0.5),
            (("bpd_heads", "b2"), (k, d), 0.0)]
    return out


class Consts(NamedTuple):
    heads: int
    kv: int
    hd: int
    eps: float
    theta: float
    vocab: int


def consts(c: Dict) -> Consts:
    m = dims(c)
    return Consts(heads=m["heads"], kv=m["kv"], hd=m["hd"],
                  eps=float(c["rms_norm_eps"]), theta=float(c["rope_theta"]),
                  vocab=m["vocab"])


@functools.partial(jax.jit, static_argnames=("k", "fp8"))
def _layer(bp, h, *, k: Consts, fp8: bool):
    s = h.shape[0]
    x = _rmsnorm(h, bp["ln1"]["scale"], k.eps)
    q = _rope(_mm("sd,dhk->shk", x, bp["attn"]["wq"], fp8), k.theta)
    kk = _rope(_mm("sd,dhk->shk", x, bp["attn"]["wk"], fp8), k.theta)
    v = _mm("sd,dhk->shk", x, bp["attn"]["wv"], fp8)
    kk = jnp.repeat(kk, k.heads // k.kv, axis=1)
    v = jnp.repeat(v, k.heads // k.kv, axis=1)
    scores = jnp.einsum("shd,thd->hst", q, kk, precision=HIGHEST)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores / math.sqrt(k.hd),
                                     -jnp.inf), axis=-1)
    ctx = jnp.einsum("hst,thd->shd", probs, v, precision=HIGHEST)
    h = h + _mm("shk,hkd->sd", ctx, bp["attn"]["wo"], fp8)
    x = _rmsnorm(h, bp["ln2"]["scale"], k.eps)
    y = jax.nn.silu(_mm("sd,df->sf", x, bp["mlp"]["w1"]["w"], fp8)) \
        * _mm("sd,df->sf", x, bp["mlp"]["w3"]["w"], fp8)
    return h + _mm("sf,fd->sd", y, bp["mlp"]["w2"]["w"], fp8)


def hidden_states(params: Dict, c: Dict, seqs: List[List[int]], *,
                  length: int, fp8: bool = False):
    k = consts(c)
    table = params["embed"]["table"]
    out = []
    for s in seqs:
        row = np.zeros((bucketed(length),), np.int32)
        row[:len(s)] = s
        h = table[jnp.asarray(row)].astype(F32)
        for bp in params["blocks"]:
            h = _layer(bp, h, k=k, fp8=fp8)
        out.append(h)
    return out


@functools.partial(jax.jit, static_argnames=("k", "fp8"))
def _head(final_scale, w, h, *, k: Consts, fp8: bool):
    return _mm("sd,dv->sv", _rmsnorm(h, final_scale, k.eps), w[:, :k.vocab],
               fp8)


def logits(params: Dict, c: Dict, h, *, fp8: bool = False):
    return _head(params["final_norm"]["scale"], params["lm_head"]["w"], h,
                 k=consts(c), fp8=fp8)


def _trunk_params(m: Dict) -> int:
    d, hd = m["d"], m["hd"]
    return (2 * d * m["heads"] * hd + 2 * d * m["kv"] * hd
            + 3 * d * m["ff"])


def verify_step(c: Dict, contexts: Sequence[int]) -> Dict:
    m = dims(c)
    b, k = len(contexts), m["k"]
    attn = sum(4 * m["layers"] * k * (ctx + k) * m["heads"] * m["hd"]
               for ctx in contexts)
    flops = (2 * b * k * m["layers"] * _trunk_params(m) + attn
             + 2 * b * (k - 1) * 2 * m["d"] * m["dh"]
             + 2 * b * 2 * k * m["d"] * m["vocab"])
    # the untied head is read whole, the embedding table only at the
    # B·k rows the step embeds
    weights = (m["layers"] * _trunk_params(m) + m["vocab"] * m["d"]
               + b * k * m["d"] + 2 * m["d"] * k * m["dh"])
    kv = sum(m["layers"] * 2 * m["kv"] * m["hd"] * ctx for ctx in contexts)
    return {"flops": flops, "bytes": BYTES * (weights + kv)}


def greedy_flops_per_token(c: Dict, context: float) -> float:
    m = dims(c)
    return (2 * m["layers"] * _trunk_params(m) + 2 * m["d"] * m["vocab"]
            + 4 * m["layers"] * context * m["heads"] * m["hd"])
