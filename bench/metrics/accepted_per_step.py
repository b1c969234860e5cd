"""Decode step: tokens committed (streamed to clients) over slot-steps,
the active slots summed over the engine steps of the traced part of the
window (tokens/step)."""


def read(run):
    slot_steps = sum(len(ctx) for ctx in run["steps"])
    return run["tokens_streamed"] / slot_steps if slot_steps else None
