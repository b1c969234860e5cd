"""Whole step on the chip: the FLOPs one verify step's work requires
(``bench.flops.verify_step``) over the measured device time per step and
the chips' peak (%).  Bounds what any kernel inside the step can claim."""
from bench import flops


def read(run):
    prog = ((run["trace"] or {}).get("programs") or {}).get("step_windowed")
    steps = [ctx for ctx in run["steps"] if ctx]
    if not prog or not prog["count"] or not steps:
        return None
    work = sum(flops.verify_step(run, ctx)["flops"]
               for ctx in steps) / len(steps)
    peak = run["chips"] * run["peaks"]["bf16_flop_per_s"]
    return 100.0 * work / peak / (prog["seconds"] / prog["count"])
