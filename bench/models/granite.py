"""Granite (Hugging Face ``GraniteForCausalLM``, ``model_type`` "granite"):
everything in the benchmark that knows this model's shape.

A configuration file whose ``model_type`` is "granite" is read here, and
nowhere else: the program's ``ModelConfig`` for it, the layout of the
weights the benchmark draws, the plain reference forward and the work a
decoding step requires.

The reference is written from the published architecture and the
configuration file alone; it imports nothing of the program.  One sequence
at a time, one layer at a time, in float32, every weight product through
``bench.reference._mm`` (so ``fp8=True`` is the float8 control):

    h  = embed[tokens] * embedding_multiplier
    h += residual_multiplier * attn(rmsnorm(h))      (GQA, RoPE, causal,
                                                     scores * attention_multiplier)
    h += residual_multiplier * down(silu(gate(x)) * up(x)),  x = rmsnorm(h)
    logits = rmsnorm(h) @ embed[:vocab].T / logits_scaling

The work (``verify_step``, ``greedy_flops_per_token``) counts a dense GQA
trunk with a tied vocabulary table, as ``bench.flops`` says what is
counted: a verify step of B rows, block k and K heads reads all weights
once and the KV of each row's context once; it puts B·k positions through
the trunk with attention over each row's context, B·(K-1) positions
through the head FFNs (head 1 is the identity) and B·(k+K) rows through
the vocabulary.  One greedy decoding step per committed token is the
trunk, the unembedding and attention over the token's context.  Bytes are
of the served dtype (bf16, 2 bytes).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec
from bench.reference import F32, HIGHEST, _mm, _rmsnorm, _rope, bucketed

BYTES = 2


def program_config(c: Dict):
    """The program's ``ModelConfig`` for a configuration file: the
    registry entry with the file's sizes.  Refuses a file that states
    something the program cannot run (it has no granite multipliers)."""
    from repro.config import get_config

    hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    fixed = {"attention_multiplier": hd ** -0.5, "embedding_multiplier": 1.0,
             "residual_multiplier": 1.0, "logits_scaling": 1.0,
             "rms_norm_eps": 1e-6}
    for key, want in fixed.items():
        if not math.isclose(float(c[key]), want, rel_tol=1e-9):
            raise spec.SpecError(
                f"{key}={c[key]}: the served model computes {want} and has "
                f"no option for another value")
    cfg = get_config(c["registry"]).replace(
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=hd,
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_theta"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        bpd_k=c["bpd_heads"], bpd_hidden=c["bpd_head_hidden"],
        param_dtype=c["torch_dtype"], dtype=c["compute_dtype"])
    if cfg.padded_vocab_size != c["padded_vocab_size"]:
        raise spec.SpecError(f"program pads the vocabulary to "
                             f"{cfg.padded_vocab_size}, the file says "
                             f"{c['padded_vocab_size']}")
    return cfg


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------


def dims(c: Dict) -> Dict:
    """Sizes of a configuration file, under short names."""
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    d = c["hidden_size"]
    return {"d": d, "layers": c["num_hidden_layers"], "heads": h, "kv": kv,
            "hd": c.get("head_dim") or d // h, "ff": c["intermediate_size"],
            "vocab": c["vocab_size"], "padded_vocab": c["padded_vocab_size"],
            "k": c["bpd_heads"], "dh": c["bpd_head_hidden"]}


def layout(c: Dict) -> List[Tuple[Tuple, Tuple[int, ...], float]]:
    """(path, shape, std) of every leaf; std 0 means zeros, -1 ones.

    Scales follow the usual fan-in rule (std 1/sqrt(fan_in)), the embedding
    table 0.02, norm scales 1; the BPD heads' second layer is scaled by
    0.1, so each head starts near the identity of the residual it adds to.
    A leaf's index in this list is folded into its key
    (``bench.weights.make_params``): the order is part of the weights."""
    m = dims(c)
    d, hd, ff, k, dh = m["d"], m["hd"], m["ff"], m["k"], m["dh"]
    out = [(("embed", "table"), (m["padded_vocab"], d), 0.02)]
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


# ---------------------------------------------------------------------------
# Reference forward
# ---------------------------------------------------------------------------


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


def hidden_states(params: Dict, c: Dict, seqs: List[List[int]], *,
                  length: int, fp8: bool = False) -> List[jnp.ndarray]:
    """Last hidden state of each sequence, zero-padded to ``length``
    rounded up to ``bench.reference.BUCKET`` (one shape for every sequence
    of a cell; causal, so the padding never reaches a real position), layer
    by layer."""
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


def logits(params: Dict, c: Dict, h, *, fp8: bool = False):
    """Reference logits over the real vocabulary for hidden rows h: (S, d);
    the output head is the tied embedding table."""
    return head_logits(params["final_norm"]["scale"], params["embed"]["table"],
                       h, k=consts(c), fp8=fp8)


# ---------------------------------------------------------------------------
# Work a decoding step requires
# ---------------------------------------------------------------------------


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


def greedy_flops_per_token(c: Dict, context: float) -> float:
    m = dims(c)
    return (2 * m["layers"] * trunk_params_per_layer(m)
            + 2 * m["d"] * m["vocab"]
            + attention_flops(m, 1, context))
