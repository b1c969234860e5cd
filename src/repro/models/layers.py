"""Shared building blocks: initializers, norms, activations, RoPE, MLPs."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig

# ---------------------------------------------------------------------------
# Initializers (pure functions of a PRNG key; params are plain dict pytrees)
# ---------------------------------------------------------------------------


def dense_init(key, in_dim: int, out_dim: int, *, dtype=jnp.float32, scale: float = 1.0,
               bias: bool = False):
    std = scale / math.sqrt(in_dim)
    p = {"w": jax.random.normal(key, (in_dim, out_dim), dtype) * std}
    if bias:
        p["b"] = jnp.zeros((out_dim,), dtype)
    return p


def dense_apply(p, x):
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def norm_init(dim: int, *, kind: str = "rmsnorm", dtype=jnp.float32):
    p = {"scale": jnp.ones((dim,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((dim,), dtype)
    return p


def norm_apply(p, x, *, kind: str = "rmsnorm", eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def group_norm_apply(p, x, num_groups: int, *, eps: float = 1e-5):
    """GroupNorm over the channel dim (used by RWKV6 per-head ln_x)."""
    *lead, c = x.shape
    xf = x.astype(jnp.float32).reshape(*lead, num_groups, c // num_groups)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y.reshape(*lead, c)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------


def activation(name: str, x):
    if name == "silu":
        return jax.nn.silu(x)
    if name == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if name == "relu2":  # nemotron-4 squared ReLU
        r = jax.nn.relu(x)
        return r * r
    if name == "relu":
        return jax.nn.relu(x)
    raise ValueError(f"unknown activation {name}")


GATED_ACTIVATIONS = ("silu", "geglu")  # use w1/w3 gated form


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32."""
    return _rotate_halves(x, positions, rope_freqs(x.shape[-1], theta))


def _rotate_halves(x, positions, freqs, scale: Optional[float] = None):
    """Rotate the first half of the last dim against the second by
    ``positions * freqs``; cos and sin are multiplied by ``scale``."""
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., :, None, :]
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    y = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return y.astype(x.dtype)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature (DeepSeek-V2's ``yarn_get_mscale``)."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, s) -> np.ndarray:
    """YaRN inverse frequencies for a rotary dim ``dim`` (``s``: a
    ``config.YarnScaling``), as DeepSeek-V2's YaRN rotary embedding makes
    them: dims that turn more than ``beta_fast`` times over the original
    context keep their frequency, those that turn fewer than ``beta_slow``
    times are divided by ``factor``, and a linear ramp joins the two."""
    def correction_dim(turns: float) -> float:
        return (dim * math.log(s.original_max_position_embeddings
                               / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(s.beta_fast)), 0)
    high = min(math.ceil(correction_dim(s.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return (extra / s.factor * (1.0 - keep) + extra * keep).astype(np.float32)


def apply_rope_pairs(x: jnp.ndarray, positions: jnp.ndarray, freqs,
                     scale: float = 1.0) -> jnp.ndarray:
    """RoPE as DeepSeek-V2 applies it (``apply_rotary_pos_emb`` of HF
    ``modeling_deepseek.py``): the last dim is first de-interleaved,
    [x0, x2, x4, .., x1, x3, ..], then rotated by halves with cos and sin
    times ``scale``.  x: (..., seq, heads, dim); positions: (..., seq)."""
    d = x.shape[-1]
    x = x.reshape(x.shape[:-1] + (d // 2, 2)).swapaxes(-1, -2).reshape(x.shape)
    return _rotate_halves(x, positions, jnp.asarray(freqs), scale)


# ---------------------------------------------------------------------------
# MLP (dense feed-forward; MoE lives in moe.py)
# ---------------------------------------------------------------------------


def mlp_init(key, cfg: ModelConfig, *, d_ff: Optional[int] = None, dtype=jnp.float32):
    d_ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"w1": dense_init(ks[0], cfg.d_model, d_ff, dtype=dtype)}
    if cfg.activation in GATED_ACTIVATIONS:
        p["w3"] = dense_init(ks[1], cfg.d_model, d_ff, dtype=dtype)
    p["w2"] = dense_init(ks[2], d_ff, cfg.d_model, dtype=dtype)
    return p


def mlp_apply(p, x, *, act: str):
    h = dense_apply(p["w1"], x)
    if "w3" in p:
        h = activation("silu" if act == "geglu" else act, h) * dense_apply(p["w3"], x)
    else:
        h = activation(act, h)
    return dense_apply(p["w2"], h)


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------


def embed_init(key, vocab: int, dim: int, *, dtype=jnp.float32):
    return {"table": jax.random.normal(key, (vocab, dim), dtype) * 0.02}


def embed_apply(p, ids):
    return jnp.take(p["table"], ids, axis=0)


def unembed_apply(p, x):
    """Tied unembedding: x @ table^T."""
    return x @ p["table"].T.astype(x.dtype)
