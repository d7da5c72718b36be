"""Shared arithmetic of the device-trace metrics."""


def program(run, name):
    """(executions, device seconds) of a program in the traced slice, or
    None where the trace has none.  The seconds are the mean over the
    cell's chips (``trace_reduce.py``), so a step's time is one chip's."""
    trace = run.get("trace")
    if not trace:
        return None
    p = trace["programs"].get(name)
    if not p or not p["count"]:
        return None
    return p["count"], p["seconds"]


def decode_need_s(run):
    """(least seconds per decode step by compute, by bytes), averaged over
    the decode steps submitted in the traced slice: the whole step's need
    (the architecture's ``decode_step`` count) over the peaks of all the
    cell's chips, which share the step."""
    need, peaks = run.get("need"), run.get("peaks")
    if not need or not need["steps"] or not peaks:
        return None
    per_step = run["chips"] * need["steps"]
    return (need["flops"] / per_step / peaks["bf16_flops_per_s"],
            need["bytes"] / per_step / peaks["hbm_bytes_per_s"])
