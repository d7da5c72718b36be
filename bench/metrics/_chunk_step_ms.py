"""Model step: mean device time of one chunked-prefill slab (the
``jit_counted_chunk`` executions in the traced slice), in milliseconds."""
from _device import program


def read(run):
    p = program(run, "chunk")
    return None if p is None else 1e3 * p[1] / p[0]
