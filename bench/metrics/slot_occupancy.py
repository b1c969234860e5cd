"""Scheduler: active slots over slots, sampled at every Frontend tick of the
traced part of the window (a tick that stepped no slot counts 0) (%)."""


def read(run):
    ticks = run["ticks"]
    if not ticks:
        return None
    return 100.0 * sum(ticks) / (len(ticks) * run["num_slots"])
