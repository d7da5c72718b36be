"""Reduce a profiler trace (``.xplane.pb``) of a ``--trace 1`` run.

Reads the file with ``jax.profiler.ProfileData`` and gives, over the traced
window (the host span ``bench.window`` that the harness puts round it):

* ``busy_s``: the union of the intervals in which a device operation ran
  (the ``XLA Ops`` and ``Async XLA Ops`` lines of each ``/device:TPU:n``
  plane), averaged over the devices;
* ``programs``: per program of ``programs.json`` (matched on the jitted
  module's name before its hash), the number of executions and their summed
  device seconds;
* ``top_ops``: the device operations that took most time, by name;
* ``idle_gaps``: the longest gaps between device operations, each named by
  the harness's host span (``bench.*``) that overlaps it most.
"""
from __future__ import annotations

import json
import re
from collections import defaultdict
from pathlib import Path

PROGRAMS_FILE = Path(__file__).resolve().parent / "programs.json"
OP_LINES = ("XLA Ops", "Async XLA Ops")
WINDOW_SPAN = "bench.window"


def programs_table(path: Path = PROGRAMS_FILE) -> dict:
    with open(path) as f:
        return json.load(f)


def _union(intervals):
    """Sorted, merged copy of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def op_name(text: str) -> str:
    """``%fusion.3 = bf16[8,128]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.3 bf16[8,128]``: the op and its result shape, no layout."""
    head, _, rest = text.partition(" = ")
    head = head.lstrip("%")
    m = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return f"{head} {m.group(1)}" if m else head


def module_program(name: str, table: dict):
    """The program a jitted module's trace name belongs to, or None."""
    return table.get(name.split("(", 1)[0])


def reduce_trace(path, table: dict = None, top: int = 10) -> dict:
    from jax.profiler import ProfileData
    table = programs_table() if table is None else table
    data = ProfileData.from_file(str(path))
    window, host = None, []
    devices = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name.startswith("bench."):
                        host.append((ev.start_ns,
                                     ev.start_ns + ev.duration_ns, ev.name))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} host span")
    if not devices:
        raise ValueError(f"{path}: no /device:TPU plane")
    t0, t1 = window
    busy_ns = 0.0
    progs = defaultdict(lambda: [0, 0.0])
    ops = defaultdict(float)
    gaps = []
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        spans = []
        modules = []
        if "XLA Modules" in lines:
            for ev in lines["XLA Modules"].events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if s >= t0 and e <= t1:
                    prog = module_program(ev.name, table)
                    modules.append((s, e, prog or ev.name.split("(", 1)[0]))
                    if prog is not None:
                        progs[prog][0] += 1
                        progs[prog][1] += ev.duration_ns
        modules.sort()
        for lname in OP_LINES:
            if lname not in lines:
                continue
            for ev in lines[lname].events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e <= t0 or s >= t1:
                    continue
                spans.append((s, e))
                if lname == "XLA Ops":
                    ops[(_owner(modules, s), op_name(ev.name))] += \
                        min(e, t1) - max(s, t0)
        merged = _union(_clip(spans, t0, t1))
        busy_ns += sum(e - s for s, e in merged)
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                gaps.append((gs, ge))
    n = len(devices)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "devices": n,
        "programs": {k: {"count": c // n if n else c,
                         "seconds": ns / n / 1e9}
                     for k, (c, ns) in sorted(progs.items())},
        "top_ops": [[f"{mod}:{name}", ns / n / 1e9] for (mod, name), ns
                    in sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_host_label(host, gs, ge), (ge - gs) / 1e9]
                      for gs, ge in gaps[:top]],
    }


def _owner(modules, t):
    """Name of the module whose interval holds time ``t``."""
    lo, hi = 0, len(modules)
    while lo < hi:
        mid = (lo + hi) // 2
        if modules[mid][0] <= t:
            lo = mid + 1
        else:
            hi = mid
    if lo and modules[lo - 1][0] <= t <= modules[lo - 1][1]:
        return modules[lo - 1][2]
    return "none"


def _host_label(host, gs, ge) -> str:
    """The ``bench.*`` host span overlapping ``[gs, ge)`` most."""
    best, best_ov = "bench.none", 0.0
    for s, e, name in host:
        ov = min(e, ge) - max(s, gs)
        if ov > best_ov:
            best, best_ov = name, ov
    return best
