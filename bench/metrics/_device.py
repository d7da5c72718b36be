"""Shared arithmetic of the device-trace metrics."""


def program(run, name):
    """(executions, device seconds) of a program in the traced slice, or
    None where the trace has none."""
    trace = run.get("trace")
    if not trace:
        return None
    p = trace["programs"].get(name)
    if not p or not p["count"]:
        return None
    return p["count"], p["seconds"]


def decode_need_s(run):
    """(least seconds per decode step by compute, by bytes), averaged over
    the decode steps submitted in the traced slice, from ``counts.py`` and
    the chip's peaks."""
    need, peaks = run.get("need"), run.get("peaks")
    if not need or not need["steps"] or not peaks:
        return None
    n = need["steps"]
    return (need["flops"] / n / peaks["bf16_flops_per_s"],
            need["bytes"] / n / peaks["hbm_bytes_per_s"])
