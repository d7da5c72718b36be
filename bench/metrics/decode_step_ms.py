"""Model step: mean device time of one decode step (the ``jit_serve_step``
executions in the traced slice), in milliseconds."""
from _device import program


def read(run):
    p = program(run, "decode")
    return None if p is None else 1e3 * p[1] / p[0]
