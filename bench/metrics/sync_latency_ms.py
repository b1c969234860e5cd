"""Session device functions: per execution of the step program, the end of
the program's next ``serve.engine.sync`` span (the status pull returning on
the host) minus the execution's end on the device; median over the traced
steps (``bench.span_reduce``) (ms)."""
from bench import span_reduce


def read(run):
    return span_reduce.median_sync_ms(run)
