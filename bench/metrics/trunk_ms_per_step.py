"""Model inside the step: device time of the ``bpd.trunk`` scope (the
embedding and every layer of the k-wide verify forward, with its staged KV
writes) per execution of the step program, from the ops' ``op_name``
metadata in the trace (``bench.span_reduce``) (ms)."""
from bench import span_reduce


def read(run):
    return span_reduce.scope_ms_per_step(run, "bpd.trunk")
