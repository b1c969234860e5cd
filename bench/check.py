"""What decides ``correct``: the served tokens against the plain reference.

After the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and always holding the one with
the most served tokens, is run once through ``bench.reference`` over its
prompt and served tokens.  For each served token the gap by which the
reference's logit of that token lies below the reference's best logit at
that position is read; the widest gap is compared with its limit.  The
served tokens are what the window streamed to its clients: the first comes
from the padded admission prefill, the rest from k-wide verify steps
through the KV cache under exact acceptance, all greedy.

The control reads the same positions with the reference in a lower
precision put in the program's place: the gap of the token that the lower
precision puts first (``control_gap``).
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference


def sample(records: Sequence[Dict], seed: int, *, min_tokens: int,
           max_requests: int) -> List[Dict]:
    """Finished requests to compare: the longest, then others in an order
    drawn from the seed until ``min_tokens`` served tokens are held or
    ``max_requests`` requests."""
    done = [r for r in records if r["done"] is not None and r["error"] is None]
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: len(done[i]["tokens"]))
    order = np.random.default_rng([int(seed) % (1 << 63), 9]).permutation(
        len(done))
    picked = [done[longest]]
    for i in order:
        if (sum(len(r["tokens"]) for r in picked) >= min_tokens
                or len(picked) >= max_requests):
            break
        if i != longest:
            picked.append(done[i])
    return picked


@functools.partial(jax.jit, static_argnames=("k",))
def _gaps(final_scale, table, h, rows, served, *, k):
    """Reference logits at ``rows`` of h; the gap of each served token."""
    logits = reference.head_logits(final_scale, table, h[rows], k=k,
                                   fp8=False)
    best = jnp.max(logits, -1)
    return best - jnp.take_along_axis(logits, served[:, None], -1)[:, 0], \
        logits


@functools.partial(jax.jit, static_argnames=("k",))
def _control_gaps(final_scale, table, h_ctrl, rows, ref_logits, *, k):
    ctrl = reference.head_logits(final_scale, table, h_ctrl[rows], k=k,
                                 fp8=True)
    first = jnp.argmax(ctrl, -1)
    return jnp.max(ref_logits, -1) - jnp.take_along_axis(
        ref_logits, first[:, None], -1)[:, 0]


def _rows(n_prompt: int, n_served: int, count: int) -> np.ndarray:
    """Positions whose logits predict the served tokens, padded to
    ``count`` with the last one (so every request has one shape)."""
    rows = np.arange(n_prompt - 1, n_prompt - 1 + n_served)
    pad = np.full((count - len(rows),), rows[-1])
    return np.concatenate([rows, pad]).astype(np.int32)


def served_gaps(params: Dict, c: Dict, picked: Sequence[Dict], geo: Dict, *,
                control: bool = False) -> Dict:
    """Widest gap of the served tokens (and of the control's, if asked)
    over the picked requests, with the number of tokens compared.  ``geo``
    is the engine geometry of the cell's traffic: sequences are padded to
    ``max_prompt_len + max_new_cap``, served rows to ``max_new_cap``."""
    k = reference.consts(c)
    seqs = [r["prompt"] + r["tokens"] for r in picked]
    length = geo["max_prompt_len"] + geo["max_new_cap"]
    n_rows = reference.bucketed(geo["max_new_cap"])
    hs = reference.hidden_states(params, c, seqs, length=length)
    hc = (reference.hidden_states(params, c, seqs, length=length, fp8=True)
          if control else [None] * len(seqs))
    final, table = params["final_norm"]["scale"], params["embed"]["table"]
    widest = ctrl_widest = 0.0
    count = 0
    for r, h, h8 in zip(picked, hs, hc):
        n = len(r["tokens"])
        rows = _rows(len(r["prompt"]), n, n_rows)
        served = np.zeros((len(rows),), np.int32)
        served[:n] = r["tokens"]
        gaps, logits = _gaps(final, table, h, jnp.asarray(rows),
                             jnp.asarray(served), k=k)
        widest = max(widest, float(jnp.max(gaps[:n])))
        count += n
        if control:
            cg = _control_gaps(final, table, h8, jnp.asarray(rows), logits,
                               k=k)
            ctrl_widest = max(ctrl_widest, float(jnp.max(cg[:n])))
    out = {"served_logit_gap": widest, "tokens_compared": count,
           "requests_compared": len(picked)}
    if control:
        out["control_logit_gap"] = ctrl_widest
    return out
