"""Model inside the step: device time of the ``bpd.heads`` scope (the K
head FFNs, the vocabulary projection of every head at every verify
position) per execution of the step program, from the ops' ``op_name``
metadata in the trace (``bench.span_reduce``) (ms)."""
from bench import span_reduce


def read(run):
    return span_reduce.scope_ms_per_step(run, "bpd.heads")
