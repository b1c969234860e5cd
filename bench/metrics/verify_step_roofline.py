"""Whole step on the chip: the least time of one verify step, from the
bytes and FLOPs its work requires at the traced steps' shapes
(``bench.flops.verify_step``), over the measured device time per step (%).
"""
from bench import flops


def read(run):
    prog = ((run["trace"] or {}).get("programs") or {}).get("step_windowed")
    steps = [ctx for ctx in run["steps"] if ctx]
    if not prog or not prog["count"] or not steps:
        return None
    least = sum(flops.least_seconds(flops.verify_step(run, ctx),
                                    run["peaks"]) for ctx in steps)
    least /= len(steps) * run["chips"]
    return 100.0 * least / (prog["seconds"] / prog["count"])
