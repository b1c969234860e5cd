"""Whole step on the chip: FLOPs of one greedy decoding step per committed
token at the mean context (``bench.flops``, counted by the configuration's
family: for granite the trunk, the unembedding and attention) times the
tokens per second delivered in the traced part of the window, over chips
times peak (%)."""
from bench import flops


def read(run):
    ctxs = [x for ctx in run["steps"] for x in ctx]
    if not ctxs or not run["tokens_per_s"]:
        return None
    per_token = flops.greedy_flops_per_token(run, sum(ctxs) / len(ctxs))
    peak = run["chips"] * run["peaks"]["bf16_flop_per_s"]
    return 100.0 * per_token * run["tokens_per_s"] / peak
