"""DeepSeek-V2 (Hugging Face ``DeepseekV2ForCausalLM``, ``model_type``
"deepseek_v2"): everything in the benchmark that knows this model's shape.

A configuration file whose ``model_type`` is "deepseek_v2" is read here,
and nowhere else: the program's ``ModelConfig`` for it, the layout of the
weights the benchmark draws, the plain reference forward and the work a
decoding step requires.

The reference is written from the published architecture (arXiv:2405.04434
§2; the model's ``modeling_deepseek.py``) and the configuration file alone;
it imports nothing of the program.  One sequence at a time, one layer at a
time, in float32, every weight product through ``bench.reference._mm`` (so
``fp8=True`` is the float8 control):

    x       = rmsnorm(h)
    q       = x Wq,  [c, k_pe] = x Wkv_a,  c = rmsnorm(c)      (no q LoRA)
    k_nope  = c Wkb,  v = c Wvb                   (the expanded form)
    q_pe, k_pe: de-interleaved pairs, rotate-half RoPE at YaRN frequencies
    h      += softmax(causal(q_nope k_nope + q_pe k_pe) * scale) v Wo
    x       = rmsnorm(h)
    h      += silu(x W1) * (x W3) W2               (layers < first_k_dense)
    h      += sum over the top-k experts of softmax(x Wr) of
              gate * silu(x W1e) * (x W3e) W2e     (gates not renormalised
              + the shared experts' MLP, ungated    when norm_topk_prob is
                                                    false, else to 1)
    logits  = rmsnorm(h) Wlm                      (untied)

with scale = (nope + rope)^-1/2 * mscale(factor, mscale_all_dim)^2.  The
routed experts run one at a time over the whole sequence, each token's
unchosen experts weighted 0 (the same sum); the control rounds each
expert's weights with their own scale, as each is a tensor of its own.
``Wkb`` and ``Wvb`` are the key and value halves of ``kv_b_proj``.

The work (``verify_step``, ``greedy_flops_per_token``, ``moe_step``) counts
what a decoding step needs, whatever computes it: the absorbed MLA (W_uk
folded into the query, scores and the weighted sum taken over each row's
cached latents and RoPE keys, W_uv after), B·k·top-k routed expert rows
plus the shared experts of B·k positions, B·(K−1) head-FFN rows and
B·(k+K) vocabulary rows; bytes are every weight read once but of the
routed experts only those a step touches (the expected number of distinct
experts B·k tokens choose under uniform routing), the latent cache of each
row's context, and the embedding rows gathered.  Bytes of the served dtype
(bf16, 2 bytes).
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import math
import numpy as np

from bench import spec
from bench.reference import F32, HIGHEST, _mm, _rmsnorm, bucketed

BYTES = 2


def program_config(c: Dict):
    """The program's ``ModelConfig`` for a configuration file: the
    registry entry with the file's sizes.  Refuses what the program cannot
    run."""
    fixed = {"q_lora_rank": None, "scoring_func": "softmax",
             "topk_method": "greedy", "n_group": 1, "topk_group": 1,
             "moe_layer_freq": 1, "hidden_act": "silu",
             "attention_bias": False, "tie_word_embeddings": False}
    for key, want in fixed.items():
        if c.get(key) != want:
            raise spec.SpecError(f"{key}={c.get(key)!r}: the served model "
                                 f"runs {want!r} only")
    rs = c["rope_scaling"]
    if rs is None or rs.get("type") != "yarn":
        raise spec.SpecError(f"rope_scaling={rs!r}: the served model runs "
                             f"YaRN only")
    if c["num_key_value_heads"] != c["num_attention_heads"]:
        raise spec.SpecError("latent attention has one value head per "
                             "query head")
    try:
        from repro.config import YarnScaling, get_config
        base = get_config(c["registry"])
    except (ImportError, KeyError) as e:
        raise spec.SpecError(f"the program has no {c['registry']!r} "
                             f"(latent attention, DeepSeek routing): {e}")
    cfg = base.replace(
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        head_dim=c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
        d_ff=c["moe_intermediate_size"], dense_d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], rope_theta=float(c["rope_theta"]),
        rope_scaling=YarnScaling(
            factor=float(rs["factor"]),
            original_max_position_embeddings=int(
                rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        num_experts=c["n_routed_experts"],
        num_experts_per_tok=c["num_experts_per_tok"],
        expert_pad_multiple=1,
        num_shared_experts=c["n_shared_experts"],
        shared_expert_d_ff=c["n_shared_experts"] * c["moe_intermediate_size"],
        shared_expert_gated=False,
        norm_topk_prob=bool(c["norm_topk_prob"]),
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        first_k_dense_replace=c["first_k_dense_replace"],
        tie_embeddings=False, bpd_k=c["bpd_heads"],
        bpd_hidden=c["bpd_head_hidden"],
        param_dtype=c["torch_dtype"], dtype=c["compute_dtype"])
    if abs(float(c["rms_norm_eps"]) - 1e-6) > 1e-12:
        raise spec.SpecError(f"rms_norm_eps={c['rms_norm_eps']}: the served "
                             f"model's RMSNorm uses 1e-6")
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
    layers = c["num_hidden_layers"]
    dense = min(c["first_k_dense_replace"], layers)
    return {"d": c["hidden_size"], "layers": layers, "dense": dense,
            "moe": layers - dense, "heads": c["num_attention_heads"],
            "r": c["kv_lora_rank"], "nope": c["qk_nope_head_dim"],
            "rope": c["qk_rope_head_dim"], "v": c["v_head_dim"],
            "ffd": c["intermediate_size"], "ff": c["moe_intermediate_size"],
            "experts": c["n_routed_experts"], "topk": c["num_experts_per_tok"],
            "sff": c["n_shared_experts"] * c["moe_intermediate_size"],
            "vocab": c["vocab_size"], "padded_vocab": c["padded_vocab_size"],
            "k": c["bpd_heads"], "dh": c["bpd_head_hidden"]}


def layout(c: Dict) -> List[Tuple[Tuple, Tuple[int, ...], float]]:
    """(path, shape, std) of every leaf; std 0 means zeros, -1 ones.

    Scales follow the fan-in rule (std 1/sqrt(fan_in)), the embedding
    table 0.02, norm scales 1; the BPD heads' second layer is scaled by
    0.1, so each head starts near the identity of the residual it adds to.
    A leaf's index in this list is folded into its key
    (``bench.weights.make_params``): the order is part of the weights."""
    m = dims(c)
    d, h, r, k, dh = m["d"], m["heads"], m["r"], m["k"], m["dh"]
    qk = m["nope"] + m["rope"]
    out = [(("embed", "table"), (m["padded_vocab"], d), 0.02)]
    for i in range(m["layers"]):
        b = ("blocks", i)
        out += [
            (b + ("ln1", "scale"), (d,), -1.0),
            (b + ("attn", "wq"), (d, h, qk), d ** -0.5),
            (b + ("attn", "wkv_a"), (d, r + m["rope"]), d ** -0.5),
            (b + ("attn", "kv_norm", "scale"), (r,), -1.0),
            (b + ("attn", "wkb"), (r, h, m["nope"]), r ** -0.5),
            (b + ("attn", "wvb"), (r, h, m["v"]), r ** -0.5),
            (b + ("attn", "wo"), (h, m["v"], d), (h * m["v"]) ** -0.5),
            (b + ("ln2", "scale"), (d,), -1.0),
        ]
        if i < m["dense"]:
            out += [
                (b + ("mlp", "w1", "w"), (d, m["ffd"]), d ** -0.5),
                (b + ("mlp", "w3", "w"), (d, m["ffd"]), d ** -0.5),
                (b + ("mlp", "w2", "w"), (m["ffd"], d), m["ffd"] ** -0.5),
            ]
        else:
            e, ff, sff = m["experts"], m["ff"], m["sff"]
            out += [
                (b + ("moe", "router", "w"), (d, e), d ** -0.5),
                (b + ("moe", "w1"), (e, d, ff), d ** -0.5),
                (b + ("moe", "w3"), (e, d, ff), d ** -0.5),
                (b + ("moe", "w2"), (e, ff, d), ff ** -0.5),
                (b + ("moe", "shared", "w1", "w"), (d, sff), d ** -0.5),
                (b + ("moe", "shared", "w3", "w"), (d, sff), d ** -0.5),
                (b + ("moe", "shared", "w2", "w"), (sff, d), sff ** -0.5),
            ]
    out += [(("final_norm", "scale"), (d,), -1.0),
            (("lm_head", "w"), (d, m["padded_vocab"]), d ** -0.5),
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
    r: int
    nope: int
    rope: int
    eps: float
    scale: float
    rope_mscale: float
    inv_freq: Tuple[float, ...]
    topk: int
    norm_topk: bool
    routed_scale: float
    vocab: int


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _yarn_inv_freq(dim: int, base: float, rs: Dict) -> Tuple[float, ...]:
    """DeepSeek-V2's YaRN frequencies: the original frequency where a dim
    turns more than ``beta_fast`` times over the original context, divided
    by ``factor`` where it turns fewer than ``beta_slow`` times, a linear
    ramp between."""
    def corr(turns):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (turns * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    keep = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return tuple(float(x) for x in
                 extra / rs["factor"] * (1 - keep) + extra * keep)


def consts(c: Dict) -> Consts:
    m, rs = dims(c), c["rope_scaling"]
    scale = (m["nope"] + m["rope"]) ** -0.5
    if rs.get("mscale_all_dim"):
        scale *= _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return Consts(heads=m["heads"], r=m["r"], nope=m["nope"], rope=m["rope"],
                  eps=float(c["rms_norm_eps"]), scale=scale,
                  rope_mscale=(_mscale(rs["factor"], rs["mscale"])
                               / _mscale(rs["factor"],
                                         rs["mscale_all_dim"])),
                  inv_freq=_yarn_inv_freq(m["rope"], float(c["rope_theta"]),
                                          rs),
                  topk=m["topk"], norm_topk=bool(c["norm_topk_prob"]),
                  routed_scale=float(c["routed_scaling_factor"]),
                  vocab=m["vocab"])


def _rope(x, k: Consts):
    """x: (S, heads, rope): the pairs (2i, 2i+1) de-interleaved, then the
    rotate-half rotation with cos and sin times ``rope_mscale``."""
    s, h, d = x.shape
    ang = jnp.arange(s, dtype=F32)[:, None] * jnp.asarray(k.inv_freq, F32)
    emb = jnp.concatenate([ang, ang], -1)
    cos = (jnp.cos(emb) * k.rope_mscale)[:, None, :]
    sin = (jnp.sin(emb) * k.rope_mscale)[:, None, :]
    x = x.reshape(s, h, d // 2, 2).transpose(0, 1, 3, 2).reshape(s, h, d)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def _attention(p, x, k: Consts, fp8: bool):
    s = x.shape[0]
    q = _mm("sd,dhk->shk", x, p["wq"], fp8)
    q_nope, q_pe = q[..., :k.nope], _rope(q[..., k.nope:], k)
    kv = _mm("sd,dc->sc", x, p["wkv_a"], fp8)
    c = _rmsnorm(kv[:, :k.r], p["kv_norm"]["scale"], k.eps)
    k_pe = _rope(kv[:, None, k.r:], k)[:, 0]
    k_nope = _mm("sc,chk->shk", c, p["wkb"], fp8)
    v = _mm("sc,chk->shk", c, p["wvb"], fp8)
    scores = (jnp.einsum("qhk,shk->hqs", q_nope, k_nope, precision=HIGHEST)
              + jnp.einsum("qhk,sk->hqs", q_pe, k_pe, precision=HIGHEST))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores * k.scale, -jnp.inf), -1)
    ctx = jnp.einsum("hqs,shk->qhk", probs, v, precision=HIGHEST)
    return _mm("qhk,hkd->qd", ctx, p["wo"], fp8)


def _mlp(p, x, fp8: bool):
    gate = _mm("sd,df->sf", x, p["w1"]["w"], fp8)
    up = _mm("sd,df->sf", x, p["w3"]["w"], fp8)
    return _mm("sf,fd->sd", jax.nn.silu(gate) * up, p["w2"]["w"], fp8)


def _moe(p, x, k: Consts, fp8: bool):
    probs = jax.nn.softmax(_mm("sd,de->se", x, p["router"]["w"], fp8), -1)
    top, idx = jax.lax.top_k(probs, k.topk)
    top = (top / jnp.sum(top, -1, keepdims=True) if k.norm_topk
           else top * k.routed_scale)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], idx].set(top)             # (S, E)

    def expert(y, e):
        w1, w3, w2, g = e
        h = jax.nn.silu(_mm("sd,df->sf", x, w1, fp8)) \
            * _mm("sd,df->sf", x, w3, fp8)
        return y + g[:, None] * _mm("sf,fd->sd", h, w2, fp8), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (p["w1"], p["w3"], p["w2"], gates.T))
    return y + _mlp(p["shared"], x, fp8)


@functools.partial(jax.jit, static_argnames=("k", "fp8"))
def dense_layer(bp, h, *, k: Consts, fp8: bool):
    """One layer with a dense MLP over one sequence h: (S, d) float32."""
    h = h + _attention(bp["attn"], _rmsnorm(h, bp["ln1"]["scale"], k.eps),
                       k, fp8)
    return h + _mlp(bp["mlp"], _rmsnorm(h, bp["ln2"]["scale"], k.eps), fp8)


@functools.partial(jax.jit, static_argnames=("k", "fp8"))
def moe_layer(bp, h, *, k: Consts, fp8: bool):
    """One layer with routed and shared experts over one sequence h."""
    h = h + _attention(bp["attn"], _rmsnorm(h, bp["ln1"]["scale"], k.eps),
                       k, fp8)
    return h + _moe(bp["moe"], _rmsnorm(h, bp["ln2"]["scale"], k.eps), k,
                    fp8)


@jax.jit
def embed(table, tokens):
    return table[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("k", "fp8"))
def head_logits(final_scale, w, h, *, k: Consts, fp8: bool):
    """Logits over the real vocabulary for rows h: (S, d)."""
    x = _rmsnorm(h, final_scale, k.eps)
    return _mm("sd,dv->sv", x, w[:, :k.vocab], fp8)


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
    hs = [embed(params["embed"]["table"], jnp.asarray(r)) for r in rows]
    for i, bp in enumerate(params["blocks"]):
        layer = dense_layer if "mlp" in bp else moe_layer
        hs = [layer(bp, h, k=k, fp8=fp8) for h in hs]
    return hs


def logits(params: Dict, c: Dict, h, *, fp8: bool = False):
    """Reference logits over the real vocabulary for hidden rows h: (S, d);
    the output head is the untied ``lm_head``."""
    return head_logits(params["final_norm"]["scale"], params["lm_head"]["w"],
                       h, k=consts(c), fp8=fp8)


# ---------------------------------------------------------------------------
# Work a decoding step requires
# ---------------------------------------------------------------------------


def attn_params(m: Dict) -> int:
    """Weights of one latent attention, all applied once per position in
    the absorbed form (W_uk to the query, W_uv after the weighted sum)."""
    d, h = m["d"], m["heads"]
    return (d * h * (m["nope"] + m["rope"]) + d * (m["r"] + m["rope"])
            + m["r"] * h * (m["nope"] + m["v"]) + h * m["v"] * d)


def expert_params(m: Dict) -> int:
    return 3 * m["d"] * m["ff"]


def active_params(m: Dict) -> int:
    """Trunk weights one position passes through: every attention, the
    dense MLPs, and in each MoE layer the router, top-k experts and the
    shared experts."""
    moe = m["d"] * m["experts"] + m["topk"] * expert_params(m) \
        + 3 * m["d"] * m["sff"]
    return (m["layers"] * attn_params(m) + m["dense"] * 3 * m["d"] * m["ffd"]
            + m["moe"] * moe)


def experts_touched(m: Dict, tokens: int) -> float:
    """Expected distinct routed experts of one layer that ``tokens``
    tokens, each choosing top-k of them uniformly, touch."""
    return m["experts"] * (1.0 - (1.0 - m["topk"] / m["experts"]) ** tokens)


def weight_bytes(m: Dict, tokens: int) -> float:
    """Every weight read once, of the routed experts those touched."""
    routed = m["moe"] * experts_touched(m, tokens) * expert_params(m)
    rest = (m["layers"] * attn_params(m) + m["dense"] * 3 * m["d"] * m["ffd"]
            + m["moe"] * (m["d"] * m["experts"] + 3 * m["d"] * m["sff"])
            + m["d"] * m["vocab"] + 2 * m["d"] * m["k"] * m["dh"])
    return BYTES * (routed + rest + tokens * m["d"])


def latent_bytes(m: Dict, context: int) -> int:
    return BYTES * m["layers"] * (m["r"] + m["rope"]) * context


def attention_flops(m: Dict, queries: int, context: int) -> int:
    """Absorbed MLA of ``queries`` positions over ``context`` cached
    latents, all layers: scores over latent and RoPE key, the weighted sum
    of latents."""
    return (2 * m["layers"] * queries * context * m["heads"]
            * (2 * m["r"] + m["rope"]))


def verify_step(c: Dict, contexts: Sequence[int]) -> Dict:
    """FLOPs and bytes one verify step of ``len(contexts)`` active rows
    requires."""
    m = dims(c)
    b, k = len(contexts), m["k"]
    flops = 2 * b * k * active_params(m)
    flops += sum(attention_flops(m, k, ctx + k) for ctx in contexts)
    flops += 2 * b * (k - 1) * 2 * m["d"] * m["dh"]
    flops += 2 * b * (k + k) * m["d"] * m["vocab"]
    nbytes = weight_bytes(m, b * k) + sum(latent_bytes(m, ctx)
                                          for ctx in contexts)
    return {"flops": flops, "bytes": nbytes}


def moe_step(c: Dict, contexts: Sequence[int]) -> Dict:
    """FLOPs and bytes of a verify step's routed experts alone (what the
    ``moe.experts`` scope has to do): B·k·top-k expert rows, and the
    weights of the experts they touch, read once."""
    m = dims(c)
    tokens = len(contexts) * m["k"]
    return {"flops": 2 * m["moe"] * tokens * m["topk"] * expert_params(m),
            "bytes": BYTES * m["moe"] * experts_touched(m, tokens)
            * expert_params(m)}


def greedy_flops_per_token(c: Dict, context: float) -> float:
    m = dims(c)
    return (2 * active_params(m) + 2 * m["d"] * m["vocab"]
            + attention_flops(m, 1, context))
