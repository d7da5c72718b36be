"""Whole decode step: FLOPs a decode step needs (the architecture's count)
over the mean device time of a step times the peak bf16 FLOP/s of all the
cell's chips, in percent."""
from _device import decode_need_s, program


def read(run):
    p, need = program(run, "decode"), decode_need_s(run)
    if p is None or need is None:
        return None
    return 100.0 * need[0] / (p[1] / p[0])
