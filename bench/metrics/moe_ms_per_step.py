"""Model inside the step: device time of the ops under the ``moe.*`` scopes
(route, dispatch, experts, combine, shared) per execution of the step
program, from the ops' ``op_name`` in the trace (``bench.model_scopes``)
(ms)."""
from bench import model_scopes


def read(run):
    return model_scopes.prefix_ms_per_step(run, "moe.")
