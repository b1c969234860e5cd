"""Model inside the step: the least time of a verify step's routed-expert
work (``moe_step`` of the configuration's family: B·k·top-k expert rows
and the weights of the experts they touch, whichever of FLOPs and bytes
takes longer at the chip's peaks) over the device time per step of the
``moe.experts`` scope (%).  It reads the work that is needed, whatever
computes it."""
from bench import flops, model_scopes


def read(run):
    work = getattr(run["model"], "moe_step", None)
    steps = [ctx for ctx in run["steps"] if ctx]
    seconds = model_scopes.scope_s_per_step(run, "moe.experts")
    if work is None or not steps or not seconds:
        return None
    least = sum(flops.least_seconds(work(run["config"], ctx), run["peaks"])
                for ctx in steps) / (len(steps) * run["chips"])
    return 100.0 * least / seconds
