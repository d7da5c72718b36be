"""Ring-buffer lifecycle tracer — per-request spans, events and counters.

The serving engine (``serve.scheduler``) and the paged cache backend
(``serve.kvcache``) feed a ``Tracer`` with the full life of every request
(submit -> admit/defer -> prefill slabs -> first token -> decode -> finish)
plus allocator events (page alloc/free, prefix hits, copy-on-write, LRU
eviction) and counter samples (queue depth, pool occupancy).  Recording is
a single tuple append into a bounded ``deque`` — cheap enough to leave on
during a soak — and ``None`` tracers cost one attribute check per site.

Two export formats:

  * ``to_jsonl``  — one event per line, trivially greppable/joinable.
  * ``to_chrome`` — Chrome trace-event JSON (``{"traceEvents": [...]}``)
    that opens directly in Perfetto / ``chrome://tracing``: one thread
    track per engine slot (request spans + chunk slabs), plus dedicated
    ``queue`` / ``allocator`` / ``engine`` tracks and counter tracks.

Timestamps are ``time.perf_counter()`` seconds relative to tracer creation
(exported as microseconds, the Chrome unit).

``Phase`` is the engine's one phase mechanism: each phase of an engine
cycle is stamped once and sent to every sink present — a
``jax.profiler.TraceAnnotation`` named ``engine.<phase>`` (so a profiler
trace shows the phases on the device trace's clock, beside the device ops
they dispatched), the ``DispatchProfiler`` phase, and a ``Tracer`` span.
``annotate_gc`` adds an ``engine.gc`` annotation round every Python
garbage collection.
"""
from __future__ import annotations

import gc
import json
import time
from collections import Counter, deque
from typing import Any, Dict, Iterable, List, Optional, Tuple, Union

from jax.profiler import StepTraceAnnotation, TraceAnnotation

# event phases (Chrome trace-event vocabulary)
INSTANT = "i"
SPAN = "X"
COUNTER = "C"

Track = Union[int, str]          # int: engine slot; str: named track


class Tracer:
    """Bounded ring buffer of trace events.

    ``capacity`` bounds memory: once full, the oldest events are dropped
    (``dropped`` counts them) — a long-lived engine can trace forever and
    export the most recent window.
    """

    def __init__(self, capacity: int = 1 << 16):
        self.capacity = capacity
        self._ring: deque = deque(maxlen=capacity)
        self.dropped = 0
        self._t0 = time.perf_counter()

    # ------------------------------------------------------------ record
    def now(self) -> float:
        return time.perf_counter() - self._t0

    def rel(self, t_abs: float) -> float:
        """An absolute ``perf_counter`` stamp -> tracer-relative seconds."""
        return t_abs - self._t0

    def _push(self, evt: Tuple):
        if len(self._ring) == self.capacity:
            self.dropped += 1
        self._ring.append(evt)

    def instant(self, name: str, track: Track, rid: Optional[int] = None,
                ts: Optional[float] = None, **args):
        """A point event (e.g. ``submit``, ``page_alloc``, ``evict``)."""
        self._push((ts if ts is not None else self.now(), INSTANT, name,
                    track, rid, 0.0, args or None))

    def span(self, name: str, track: Track, start: float, end: float,
             rid: Optional[int] = None, **args):
        """A complete [start, end) span (e.g. a request, a chunk slab)."""
        self._push((start, SPAN, name, track, rid, max(end - start, 0.0),
                    args or None))

    def counter(self, name: str, value, ts: Optional[float] = None):
        """A counter sample (queue depth, pages in use, ...)."""
        self._push((ts if ts is not None else self.now(), COUNTER, name,
                    name, None, 0.0, {"value": value}))

    # ------------------------------------------------------------ inspect
    def events(self, name: Optional[str] = None) -> List[Tuple]:
        """Snapshot of recorded events ``(ts, ph, name, track, rid, dur,
        args)``, oldest first; ``name`` filters."""
        evs = list(self._ring)
        if name is not None:
            evs = [e for e in evs if e[2] == name]
        return evs

    def counts(self) -> Dict[str, int]:
        """Event-name -> occurrence count (allocator balance checks)."""
        return dict(Counter(e[2] for e in self._ring))

    def sum_arg(self, name: str, key: str) -> float:
        """Sum ``args[key]`` over events called ``name`` (e.g. total pages
        allocated = ``sum_arg("page_alloc", "pages")``)."""
        return sum(e[6][key] for e in self._ring
                   if e[2] == name and e[6] and key in e[6])

    def clear(self):
        self._ring.clear()
        self.dropped = 0

    # ------------------------------------------------------------- export
    def to_jsonl(self, path: str):
        with open(path, "w") as f:
            for ts, ph, name, track, rid, dur, args in self._ring:
                rec = {"ts_us": ts * 1e6, "ph": ph, "name": name,
                       "track": track}
                if rid is not None:
                    rec["rid"] = rid
                if ph == SPAN:
                    rec["dur_us"] = dur * 1e6
                if args:
                    rec["args"] = args
                f.write(json.dumps(rec) + "\n")
            # trailing metadata record: a truncated ring is not a complete
            # trace, and consumers must be able to tell
            f.write(json.dumps({"ph": "M", "name": "dropped_events",
                                "dropped": self.dropped,
                                "capacity": self.capacity}) + "\n")

    def _tids(self) -> Dict[Track, int]:
        """Stable track -> tid map: slot ints keep their value (one track
        per slot, sorted first in Perfetto); named tracks follow."""
        slots = sorted({e[3] for e in self._ring if isinstance(e[3], int)})
        named = sorted({e[3] for e in self._ring
                        if isinstance(e[3], str) and e[1] != COUNTER})
        tids: Dict[Track, int] = {s: s for s in slots}
        base = (max(slots) + 1) if slots else 0
        for i, n in enumerate(named):
            tids[n] = base + 100 + i
        return tids

    def chrome_events(self, pid: int = 1) -> List[Dict[str, Any]]:
        tids = self._tids()
        out: List[Dict[str, Any]] = [
            {"ph": "M", "pid": pid, "name": "process_name",
             "args": {"name": "repro.serve"}},
            {"ph": "M", "pid": pid, "name": "dropped_events",
             "args": {"dropped": self.dropped,
                      "capacity": self.capacity}}]
        if self.dropped:
            # visible Perfetto counter: the exported window starts after
            # `dropped` older events fell off the ring
            first_ts = self._ring[0][0] if self._ring else 0.0
            out.append({"ph": COUNTER, "pid": pid, "name": "dropped_events",
                        "ts": first_ts * 1e6,
                        "args": {"value": self.dropped}})
        for track, tid in tids.items():
            label = f"slot {track}" if isinstance(track, int) else track
            out.append({"ph": "M", "pid": pid, "tid": tid,
                        "name": "thread_name", "args": {"name": label}})
            out.append({"ph": "M", "pid": pid, "tid": tid,
                        "name": "thread_sort_index",
                        "args": {"sort_index": tid}})
        for ts, ph, name, track, rid, dur, args in self._ring:
            evt: Dict[str, Any] = {"ph": ph, "name": name, "pid": pid,
                                   "ts": ts * 1e6}
            if ph == COUNTER:
                evt["args"] = args
            else:
                evt["tid"] = tids.get(track, 0)
                evt["cat"] = "serve"
                a = dict(args) if args else {}
                if rid is not None:
                    a["rid"] = rid
                if a:
                    evt["args"] = a
                if ph == SPAN:
                    evt["dur"] = dur * 1e6
            out.append(evt)
        return out

    def to_chrome(self, path: str):
        """Write a Perfetto-loadable Chrome trace-event file."""
        with open(path, "w") as f:
            json.dump({"traceEvents": self.chrome_events(),
                       "displayTimeUnit": "ms"}, f)


class Phase:
    """One phase of the serving engine, stamped once on the host's
    ``perf_counter`` clock and sent to every sink present:

    * ``TraceAnnotation("engine.<name>")`` (``step=n``: a
      ``StepTraceAnnotation`` with ``step_num=n``), recorded when a
      ``jax.profiler`` trace runs; otherwise it costs a check of the
      profiler's enabled flag;
    * the ``DispatchProfiler`` phase ``prof`` (keyed ``key``), when
      ``profiler`` is given;
    * the ``Tracer`` span ``span`` on ``track`` with ``args``, when
      ``tracer`` is given.

    ``t0`` and ``t1`` are the two stamps (``t1`` set on exit), read by the
    engine's own time counters."""

    __slots__ = ("_ann", "_prof", "_tracer", "_span", "_track", "_args",
                 "t0", "t1")

    def __init__(self, name: str, *, step: Optional[int] = None,
                 profiler=None, prof: Optional[str] = None, key: Any = None,
                 tracer: Optional[Tracer] = None, span: Optional[str] = None,
                 track: Track = "engine", **args):
        name = "engine." + name
        self._ann = (TraceAnnotation(name) if step is None
                     else StepTraceAnnotation(name, step_num=step))
        self._prof = (profiler.phase(prof, key=key)
                      if profiler is not None and prof is not None else None)
        self._tracer = tracer if span is not None else None
        self._span, self._track, self._args = span, track, args

    def __enter__(self) -> "Phase":
        self._ann.__enter__()
        if self._prof is not None:
            self._prof.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.perf_counter()
        try:
            if self._prof is not None:
                self._prof.__exit__(*exc)
            tr = self._tracer
            if tr is not None:
                tr.span(self._span, self._track, tr.rel(self.t0),
                        tr.rel(self.t1), **self._args)
        finally:
            self._ann.__exit__(*exc)
        return False


_gc_open: List[TraceAnnotation] = []     # the collection running now


def _gc_phase(phase: str, info: Dict[str, int]):
    if phase == "start":
        ann = TraceAnnotation("engine.gc", generation=info["generation"])
        ann.__enter__()
        _gc_open.append(ann)
    elif _gc_open:
        _gc_open.pop().__exit__(None, None, None)


def annotate_gc():
    """Put an ``engine.gc`` annotation (with the collected generation)
    round every Python garbage collection, process-wide; idempotent.  Costs
    nothing while no collection runs."""
    if _gc_phase not in gc.callbacks:
        gc.callbacks.append(_gc_phase)


def span_pairs(events: Iterable[Tuple], open_name: str,
               close_name: str) -> Tuple[Dict[int, float], Dict[int, float]]:
    """(rid -> open ts, rid -> close ts) over instant events — the test
    helper behind 'every admitted request has a closed span'."""
    opened: Dict[int, float] = {}
    closed: Dict[int, float] = {}
    for ts, ph, name, track, rid, dur, args in events:
        if rid is None:
            continue
        if name == open_name and rid not in opened:
            opened[rid] = ts
        elif name == close_name:
            closed[rid] = ts
    return opened, closed
