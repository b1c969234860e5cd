"""Random weights, made by the benchmark, not by the program.

The reference reads these same arrays, so the program and the reference
see one set of weights that neither of them made.  The tree is laid out as
the served model takes its parameters (``repro.models.model``); ``run.py``
checks the layout against the program's own before serving.  All leaves
are drawn on the device in one jitted call, in the type they are served
in, and with given shardings straight into them.

Scales follow the usual fan-in rule (std 1/sqrt(fan_in)), the embedding
table 0.02, norm scales 1; the BPD heads' second layer is scaled by 0.1, so
each head starts near the identity of the residual it adds to.

A configuration is one model, as a deployment serves one: every run draws
it from ``WEIGHTS_SEED``.  Weights drawn from the run's seed would change
how many proposals each verify step accepts, and so the work.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
WEIGHTS_SEED = 3141592653


def dims(c: Dict) -> Dict:
    """Sizes of a configuration file, under short names."""
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    d = c["hidden_size"]
    return {"d": d, "layers": c["num_hidden_layers"], "heads": h, "kv": kv,
            "hd": c.get("head_dim") or d // h, "ff": c["intermediate_size"],
            "vocab": c["vocab_size"], "padded_vocab": c["padded_vocab_size"],
            "k": c["bpd_heads"], "dh": c["bpd_head_hidden"]}


def layout(c: Dict) -> List[Tuple[Tuple, Tuple[int, ...], float]]:
    """(path, shape, std) of every leaf; std 0 means zeros, -1 ones."""
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


def _nest(leaves) -> Dict:
    tree: Dict = {}
    for path, val in leaves:
        node = tree
        for key in path[:-1]:
            if key == "blocks":
                node = node.setdefault("blocks", [])
                continue
            if isinstance(node, list):
                while len(node) <= key:
                    node.append({})
                node = node[key]
            else:
                node = node.setdefault(key, {})
        node[path[-1]] = val
    return tree


def seed_key(seed: int):
    """A PRNG key from a seed of any size (seeds may exceed 32 bits)."""
    seed = int(seed) % (1 << 62)
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(key, seed >> 31)


def param_structs(c: Dict) -> Dict:
    dtype = jnp.dtype(c["torch_dtype"])
    return _nest((path, jax.ShapeDtypeStruct(shape, dtype))
                 for path, shape, _ in layout(c))


def make_params(c: Dict, shardings=None) -> Dict:
    """Every leaf in one jitted program, drawn in float32 and served as the
    configuration's ``torch_dtype``."""
    dtype = jnp.dtype(c["torch_dtype"])
    spec = layout(c)

    def draw(key):
        leaves = []
        for i, (path, shape, std) in enumerate(spec):
            if std == 0.0:
                val = jnp.zeros(shape, dtype)
            elif std == -1.0:
                val = jnp.ones(shape, dtype)
            else:
                val = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                         jnp.float32) * std).astype(dtype)
            leaves.append((path, val))
        return _nest(leaves)

    fn = (jax.jit(draw) if shardings is None
          else jax.jit(draw, out_shardings=shardings))
    return fn(seed_key(WEIGHTS_SEED))
