"""Operations and bytes that the work requires, from the configuration's
shapes alone.

They count what a decoding step has to do, not what the program happens to
compute, so a later change that stops computing something needless, or
adds a kernel, is measured against the same yardstick.  Each
configuration's family (``bench/models/<model_type>.py``) counts them for
its own shapes:

* ``verify_step(c, contexts)``: ``{"flops", "bytes"}`` of one verify step
  of ``len(contexts)`` active rows, each with its context length;
* ``greedy_flops_per_token(c, context)``: one greedy decoding step per
  committed token at a mean context.

The functions here take a per-layer metric's run object, which carries the
configuration and its family.
"""
from __future__ import annotations

from typing import Dict, Sequence


def verify_step(run: Dict, contexts: Sequence[int]) -> Dict:
    return run["model"].verify_step(run["config"], contexts)


def greedy_flops_per_token(run: Dict, context: float) -> float:
    return run["model"].greedy_flops_per_token(run["config"], context)


def least_seconds(work: Dict, peaks: Dict) -> float:
    return max(work["flops"] / peaks["bf16_flop_per_s"],
               work["bytes"] / peaks["hbm_bytes_per_s"])
