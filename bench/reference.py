"""The float32 toolkit of every plain reference forward.

Each configuration's family (``bench/models/<model_type>.py``) writes its
reference forward from the published architecture and the configuration
file alone, importing nothing of the program, with these pieces: one
sequence at a time, one layer at a time, every matrix product at
``Precision.HIGHEST``, so that it fits beside the served weights.  Every
weight product goes through ``_mm``: its ``fp8=True`` is the control, both
operands rounded to float8 e4m3 (a per-tensor scale), the precision one
step below the configurations' bfloat16, so the control means the same
for every model.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
BUCKET = 256            # sequences are padded to a multiple of this


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


def bucketed(n: int) -> int:
    return -(-n // BUCKET) * BUCKET
