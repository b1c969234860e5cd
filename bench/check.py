"""What decides ``correct``: the served tokens against the plain reference.

After the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and always holding the one with
the most served tokens, is run once through the plain reference of the
configuration's family (``bench/models/<model_type>.py``, built from
``bench.reference``) over its prompt and served tokens.  For each served
token the gap by which the reference's logit of that token lies below the
reference's best logit at that position is read; the widest gap is
compared with its limit.  The served tokens are what the window streamed
to its clients: the first comes from the padded admission prefill, the
rest from k-wide verify steps through the KV cache under exact
acceptance, all greedy.

The control reads the same positions with the reference in a lower
precision put in the program's place: the gap of the token that the lower
precision puts first (``control_gap``).
"""
from __future__ import annotations

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


@jax.jit
def _gaps(logits, served):
    """The gap of each row's served token below the row's best logit."""
    return jnp.max(logits, -1) - jnp.take_along_axis(
        logits, served[:, None], -1)[:, 0]


@jax.jit
def _control_gaps(ref_logits, ctrl_logits):
    """The gap, in the reference's logits, of the token that the control
    puts first."""
    return _gaps(ref_logits, jnp.argmax(ctrl_logits, -1))


def _rows(n_prompt: int, n_served: int, count: int) -> np.ndarray:
    """Positions whose logits predict the served tokens, padded to
    ``count`` with the last one (so every request has one shape)."""
    rows = np.arange(n_prompt - 1, n_prompt - 1 + n_served)
    pad = np.full((count - len(rows),), rows[-1])
    return np.concatenate([rows, pad]).astype(np.int32)


def served_gaps(model, params: Dict, c: Dict, picked: Sequence[Dict],
                geo: Dict, *, control: bool = False) -> Dict:
    """Widest gap of the served tokens (and of the control's, if asked)
    over the picked requests, with the number of tokens compared, by the
    reference forward of the configuration's family ``model``
    (``bench/models/<model_type>.py``).  ``geo`` is the engine geometry of
    the cell's traffic: sequences are padded to ``max_prompt_len +
    max_new_cap``, served rows to ``max_new_cap``."""
    seqs = [r["prompt"] + r["tokens"] for r in picked]
    length = geo["max_prompt_len"] + geo["max_new_cap"]
    n_rows = reference.bucketed(geo["max_new_cap"])
    hs = model.hidden_states(params, c, seqs, length=length)
    hc = (model.hidden_states(params, c, seqs, length=length, fp8=True)
          if control else [None] * len(seqs))
    widest = ctrl_widest = 0.0
    count = 0
    for r, h, h8 in zip(picked, hs, hc):
        n = len(r["tokens"])
        rows = _rows(len(r["prompt"]), n, n_rows)
        served = np.zeros((len(rows),), np.int32)
        served[:n] = r["tokens"]
        logits = model.logits(params, c, h[rows])
        gaps = _gaps(logits, jnp.asarray(served))
        widest = max(widest, float(jnp.max(gaps[:n])))
        count += n
        if control:
            cg = _control_gaps(logits,
                               model.logits(params, c, h8[rows], fp8=True))
            ctrl_widest = max(ctrl_widest, float(jnp.max(cg[:n])))
    out = {"served_logit_gap": widest, "tokens_compared": count,
           "requests_compared": len(picked)}
    if control:
        out["control_logit_gap"] = ctrl_widest
    return out
