#!/usr/bin/env python3
"""Split a profiler trace of a ``--trace 1`` run by the program's own names.

``trace_reduce.py`` times whole jitted programs and names an idle gap by
the harness's ``bench.*`` span.  The engine now names what happens inside
a step: its host phases are ``engine.*`` profiler annotations
(``repro.obs.tracer.Phase``) and the model's device ops carry the
``jax.named_scope`` path they were traced under (their ``tf_op``, read by
``xplane_meta.py``).  ``reduce_scopes`` gives, over the traced window:

* ``scopes``: per program, ``{scope: device seconds}`` in self time (an
  op's time less what ops nested in it on the same line cover, so a
  layer-scan ``while`` keeps only its own overhead).  The scope is the
  innermost of ``SCOPES`` in the op's ``tf_op`` path; ops with a path but
  none of them go to ``other``, ops with no path (copies XLA inserted) to
  ``unattributed``; ``between_ops`` is the rest of the program's device
  time, in which none of its ops ran.  Only executions wholly inside the
  window count, as in ``trace_reduce``'s ``programs``, so the parts of a
  program sum to its device time there;
* ``phase_idle``: ``{host span: idle device seconds}``, each idle moment
  given to the innermost ``engine.*`` or ``bench.*`` span open then
  (``bench.none``: none);
* ``idle_gaps``: the longest idle gaps, each named by the span that holds
  most of it;
* ``top_ops``: the device ops that took most self time, named
  ``program:scope:op``.

    python bench/trace_scopes.py <file.xplane.pb>

prints ``trace_reduce.reduce_trace`` with these keys added (its
``idle_gaps`` and ``top_ops`` replaced) as one JSON object.
"""
from __future__ import annotations

import json
import sys
from collections import defaultdict
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import trace_reduce as TR  # noqa: E402
import xplane_meta as XM  # noqa: E402

SCOPES = ("embed", "attention", "mlp", "readout", "sample")
HOST_PREFIXES = ("engine.", "bench.")
NO_SPAN = "bench.none"


def scope_of(tf_op) -> str:
    """The innermost of ``SCOPES`` in a ``tf_op`` path, ``other`` for a
    path with none of them, ``unattributed`` for no path."""
    if not tf_op:
        return "unattributed"
    for part in reversed(tf_op.split("/")):
        if part in SCOPES:
            return part
    return "other"


def self_times(intervals):
    """Self time of each ``(start, end)`` interval of one line, where
    intervals nest or are disjoint: its length less the part of it that
    the intervals directly nested in it cover."""
    order = sorted(range(len(intervals)),
                   key=lambda i: (intervals[i][0], -intervals[i][1]))
    own = [e - s for s, e in intervals]
    stack = []
    for i in order:
        s, e = intervals[i]
        while stack and intervals[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, intervals[stack[-1]][1]) - s
        stack.append(i)
    return own


def innermost(spans, t0, t1):
    """``[(start, end, name)]`` cutting ``[t0, t1)`` by the innermost of
    the host ``spans`` (``(start, end, name)``) open at each moment: the
    one opened last, ``NO_SPAN`` where none is."""
    spans = sorted((max(s, t0), min(e, t1), n) for s, e, n in spans
                   if e > t0 and s < t1)
    points = sorted({t0, t1, *(x for s, e, _ in spans for x in (s, e))})
    out, active, k = [], [], 0
    for a, b in zip(points, points[1:]):
        while k < len(spans) and spans[k][0] <= a:
            active.append(spans[k])
            k += 1
        active = [sp for sp in active if sp[1] > a]
        name = max(active, key=lambda sp: (sp[0], -sp[1]))[2] \
            if active else NO_SPAN
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def _split(gap, segments, lo):
    """``{name: overlap}`` of one gap with the segments, from index
    ``lo`` on; returns it and the index where the next gap starts
    looking."""
    gs, ge = gap
    while lo < len(segments) and segments[lo][1] <= gs:
        lo += 1
    parts = defaultdict(float)
    i = lo
    while i < len(segments) and segments[i][0] < ge:
        s, e, name = segments[i]
        parts[name] += min(e, ge) - max(s, gs)
        i += 1
    return parts, lo


def reduce_scopes(path, table: dict = None, top: int = 10) -> dict:
    from jax.profiler import ProfileData
    table = TR.programs_table() if table is None else table
    data = ProfileData.from_file(str(path))
    meta = XM.read(path)
    window, host, devices = None, [], []
    for plane in data.planes:
        if plane.name.startswith(XM.DEVICE_PREFIX):
            devices.append(plane)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    end = ev.start_ns + ev.duration_ns
                    if ev.name == TR.WINDOW_SPAN:
                        window = (ev.start_ns, end)
                    elif ev.name.startswith(HOST_PREFIXES):
                        host.append((ev.start_ns, end, ev.name))
    if window is None:
        raise ValueError(f"{path}: no {TR.WINDOW_SPAN!r} host span")
    if not devices:
        raise ValueError(f"{path}: no {XM.DEVICE_PREFIX} plane")
    t0, t1 = window
    segments = innermost(host, t0, t1)
    scopes = defaultdict(lambda: defaultdict(float))
    held = defaultdict(float)         # device time of the counted executions
    ops = defaultdict(float)
    idle = defaultdict(float)
    gaps = []
    for plane in devices:
        ops_meta = meta.get(plane.name, {})
        lines = {line.name: line for line in plane.lines}
        modules = []
        if "XLA Modules" in lines:
            for ev in lines["XLA Modules"].events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e > t0 and s < t1:
                    modules.append((s, e, ev.name))
                    if s >= t0 and e <= t1:
                        held[_program(ev.name, table)] += e - s
        modules.sort()
        spans = []
        for lname in TR.OP_LINES:
            if lname not in lines:
                continue
            evs = [ev for ev in lines[lname].events
                   if ev.start_ns + ev.duration_ns > t0 and ev.start_ns < t1]
            clipped = [(max(ev.start_ns, t0),
                        min(ev.start_ns + ev.duration_ns, t1)) for ev in evs]
            spans.extend(clipped)
            if lname != "XLA Ops":
                continue
            for ev, own in zip(evs, self_times(clipped)):
                mod = _module(modules, ev.start_ns)
                if mod is None:
                    ops[("none", "", TR.op_name(ev.name))] += own
                    continue
                name = _program(mod[2], table)
                rec = ops_meta.get((XM.program_id(mod[2]), ev.name), {})
                scope = scope_of(rec.get("tf_op"))
                ops[(name, scope, TR.op_name(ev.name))] += own
                if mod[0] >= t0 and mod[1] <= t1:
                    scopes[name][scope] += own
        merged = TR._union(spans)
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        lo = 0
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge <= gs:
                continue
            parts, lo = _split((gs, ge), segments, lo)
            for name, ns in parts.items():
                idle[name] += ns
            gaps.append((ge - gs, max(parts, key=parts.get)
                         if parts else NO_SPAN))
    n = len(devices)
    for name, parts in scopes.items():
        parts["between_ops"] = held[name] - sum(parts.values())
    gaps.sort(key=lambda g: -g[0])
    return {
        "scopes": {prog: {k: ns / n / 1e9 for k, ns in sorted(d.items())}
                   for prog, d in sorted(scopes.items())},
        "phase_idle": {k: ns / n / 1e9 for k, ns in
                       sorted(idle.items(), key=lambda kv: -kv[1])},
        "idle_gaps": [[name, ns / 1e9] for ns, name in gaps[:top]],
        "top_ops": [[f"{prog}:{scope}:{op}", ns / n / 1e9]
                    for (prog, scope, op), ns
                    in sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
    }


def _program(module_name: str, table: dict) -> str:
    """A module's program name (``programs.json``), else its own name
    before the hash."""
    return TR.module_program(module_name, table) or \
        module_name.split("(", 1)[0]


def _module(modules, t):
    """The ``(start, end, name)`` module whose interval holds time ``t``,
    or None."""
    lo, hi = 0, len(modules)
    while lo < hi:
        mid = (lo + hi) // 2
        if modules[mid][0] <= t:
            lo = mid + 1
        else:
            hi = mid
    if lo and modules[lo - 1][0] <= t <= modules[lo - 1][1]:
        return modules[lo - 1]
    return None


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.split("\n\n")[-2].strip(), file=sys.stderr)
        return 2
    out = TR.reduce_trace(argv[0], top=20)
    out.update(reduce_scopes(argv[0], top=20))
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
