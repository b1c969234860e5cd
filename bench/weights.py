"""Random weights, made by the benchmark, not by the program.

The reference reads these same arrays, so the program and the reference
see one set of weights that neither of them made.  The configuration's
family (``bench/models/<model_type>.py``) lists every leaf in its
``layout``: path, shape and scale, laid out as the served model takes its
parameters (``repro.models.model``); ``bench.harness`` checks the layout
against the program's own before serving.  All leaves are drawn on the
device in one jitted call, in the type they are served in, and with given
shardings straight into them.

A configuration is one model, as a deployment serves one: every run draws
it from ``WEIGHTS_SEED``.  Weights drawn from the run's seed would change
how many proposals each verify step accepts, and so the work.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

WEIGHTS_SEED = 3141592653


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


def param_structs(model, c: Dict) -> Dict:
    """The tree of ``make_params(model, c)``, as shapes and dtypes."""
    dtype = jnp.dtype(c["torch_dtype"])
    return _nest((path, jax.ShapeDtypeStruct(shape, dtype))
                 for path, shape, _ in model.layout(c))


def make_params(model, c: Dict, shardings=None) -> Dict:
    """Every leaf of the family ``model``'s layout in one jitted program,
    drawn in float32 and served as the configuration's ``torch_dtype``.
    The i-th leaf is drawn from the key folded with i."""
    dtype = jnp.dtype(c["torch_dtype"])
    spec = model.layout(c)

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
