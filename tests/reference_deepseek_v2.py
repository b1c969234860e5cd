"""Plain float32 reference forward of DeepSeek-V2 (arXiv:2405.04434; the
published ``modeling_deepseek.py`` of deepseek-ai/DeepSeek-V2-Lite).

Straightforward ``jax.numpy`` at ``jax.default_matmul_precision("highest")``:
one sequence, no cache, no kernels, no batching, and nothing of the served
program imported.  ``c`` holds the published ``config.json`` keys
(``hidden_size``, ``kv_lora_rank``, ``rope_scaling``, ...).  Per layer:

    x      = rmsnorm(h)
    q      = x Wq                        split (qk_nope | qk_rope) per head
    ckv,kpe= x Wkv_a                     ckv = rmsnorm(ckv)
    k_nope = ckv Wkb,  v = ckv Wvb       the expanded form, every position
    q_pe, kpe: de-interleaved, then rotate-half RoPE at YaRN frequencies
    a      = softmax(causal(q_nope k_nope + q_pe kpe) * scale) v Wo
    h     += a
    x      = rmsnorm(h)
    h     += dense MLP (layers < first_k_dense_replace), else
             sum over the top-k of softmax(x Wr) of gate * expert(x)
             + the shared experts' MLP, ungated
    logits = rmsnorm(h) Wlm

Departures from the published model, each deliberate:
  * weights are given in the served model's tree (``embed/table``,
    ``blocks[i]/attn/{wq, wkv_a, kv_norm, wkb, wvb, wo}``,
    ``blocks[i]/{mlp, moe}``, ``final_norm``, ``lm_head/w``); ``wkb`` and
    ``wvb`` are the key and value halves of the published ``kv_b_proj``;
  * every routed expert is computed for every token and the unchosen ones
    are weighted 0, which is the same sum;
  * no auxiliary loss and no group-limited routing (``n_group`` 1,
    ``topk_method`` greedy, as the Lite model has it);
  * logits over the vocabulary the table holds (padding rows included).
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def rmsnorm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(c: Dict) -> np.ndarray:
    dim, base = c["qk_rope_head_dim"], float(c["rope_theta"])
    rs = c["rope_scaling"]
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rs is None:
        return extra.astype(np.float32)

    def corr(turns):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (turns * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    inter = extra / rs["factor"]
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def softmax_scale(c: Dict) -> float:
    scale = (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5
    rs = c["rope_scaling"]
    if rs is not None and rs.get("mscale_all_dim"):
        m = yarn_get_mscale(rs["factor"], rs["mscale_all_dim"])
        scale = scale * m * m
    return scale


def rope(x, c: Dict):
    """x: (S, heads, rope_dim) -> RoPE'd, in the de-interleaved order the
    published ``apply_rotary_pos_emb`` leaves it in."""
    s, h, d = x.shape
    rs = c["rope_scaling"]
    m = 1.0 if rs is None else (yarn_get_mscale(rs["factor"], rs["mscale"])
                                / yarn_get_mscale(rs["factor"],
                                                  rs["mscale_all_dim"]))
    freqs = jnp.outer(jnp.arange(s, dtype=F32), jnp.asarray(yarn_inv_freq(c)))
    emb = jnp.concatenate([freqs, freqs], -1)
    cos, sin = (jnp.cos(emb) * m)[:, None, :], (jnp.sin(emb) * m)[:, None, :]
    x = x.reshape(s, h, d // 2, 2).transpose(0, 1, 3, 2).reshape(s, h, d)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def attention(p, c: Dict, x):
    s = x.shape[0]
    nope, r = c["qk_nope_head_dim"], c["kv_lora_rank"]
    q = jnp.einsum("sd,dhk->shk", x, p["wq"].astype(F32))
    q_nope, q_pe = q[..., :nope], rope(q[..., nope:], c)
    kv = x @ p["wkv_a"].astype(F32)
    ckv = rmsnorm(kv[:, :r], p["kv_norm"]["scale"], c["rms_norm_eps"])
    k_pe = rope(kv[:, None, r:], c)
    k_nope = jnp.einsum("sc,chk->shk", ckv, p["wkb"].astype(F32))
    v = jnp.einsum("sc,chk->shk", ckv, p["wvb"].astype(F32))
    scores = (jnp.einsum("qhk,shk->hqs", q_nope, k_nope)
              + jnp.einsum("qhk,sk->hqs", q_pe, k_pe[:, 0])) * softmax_scale(c)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    ctx = jnp.einsum("hqs,shk->qhk", probs, v)
    return jnp.einsum("qhk,hkd->qd", ctx, p["wo"].astype(F32))


def mlp(p, x):
    gate = x @ p["w1"]["w"].astype(F32)
    up = x @ p["w3"]["w"].astype(F32)
    return (jax.nn.silu(gate) * up) @ p["w2"]["w"].astype(F32)


def moe(p, c: Dict, x):
    e, k = c["n_routed_experts"], c["num_experts_per_tok"]
    scores = jax.nn.softmax(x @ p["router"]["w"].astype(F32), -1)    # (S, E)
    top, idx = jax.lax.top_k(scores, k)
    if c["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    else:
        top = top * c["routed_scaling_factor"]
    weight = jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], idx].set(top)                # (S, E)
    w1, w3, w2 = (p[n][:e].astype(F32) for n in ("w1", "w3", "w2"))
    every = jnp.einsum("sef,efd->sed",
                       jax.nn.silu(jnp.einsum("sd,edf->sef", x, w1))
                       * jnp.einsum("sd,edf->sef", x, w3), w2)         # (S,E,d)
    y = jnp.einsum("se,sed->sd", weight, every)
    if c["n_shared_experts"]:
        y = y + mlp(p["shared"], x)
    return y


def forward(params: Dict, c: Dict, tokens) -> jnp.ndarray:
    """Logits (S, vocab rows of the head) of one token sequence."""
    eps = c["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        h = params["embed"]["table"][jnp.asarray(tokens)].astype(F32)
        for i, bp in enumerate(params["blocks"]):
            h = h + attention(bp["attn"], c,
                              rmsnorm(h, bp["ln1"]["scale"], eps))
            x = rmsnorm(h, bp["ln2"]["scale"], eps)
            if i < c["first_k_dense_replace"]:
                h = h + mlp(bp["mlp"], x)
            else:
                h = h + moe(bp["moe"], c, x)
        x = rmsnorm(h, params["final_norm"]["scale"], eps)
        return x @ params["lm_head"]["w"].astype(F32)
