"""Kernels (the whole decode program until the program names its scopes):
the decode step's share of its roofline, in percent.  The least time a
step needs, the larger of its FLOPs over peak bf16 FLOP/s and its bytes
over peak HBM bytes/s of all the cell's chips (the architecture's count:
weights once at the compute dtype, each live KV row once), over the mean
device time of a decode step on a chip."""
from _device import decode_need_s, program


def read(run):
    p, need = program(run, "decode"), decode_need_s(run)
    if p is None or need is None:
        return None
    return 100.0 * max(need) / (p[1] / p[0])
