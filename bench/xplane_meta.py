"""Op metadata of a profiler trace (``.xplane.pb``), read without TensorFlow.

``jax.profiler.ProfileData`` gives each device op's name and interval, not
the op's metadata.  That sits in the device plane's ``event_metadata`` map:
per op, the ``tf_op`` path of the JAX scopes it was traced under (e.g.
``jit(serve_step)/while/body/closed_call/attention/kv_gather/gather``), its
``hlo_category`` and its ``program_id``.  This module reads those maps
straight from the protobuf wire format of ``XSpace`` (tsl's
``xplane.proto``), skipping the planes' event lines by their length:

    XSpace.planes = 1;  XPlane: name = 2, lines = 3,
    event_metadata = 4 (map<int64, XEventMetadata>), stat_metadata = 5
    XEventMetadata: id = 1, name = 2, stats = 5
    XStatMetadata: id = 1, name = 2
    XStat: metadata_id = 1, double = 2, uint64 = 3, int64 = 4, str = 5,
           bytes = 6, ref = 7 (the id of a stat metadata whose name is
           the value)

``read(path)`` gives, per ``/device:TPU:n`` plane, ``{(program_id, op
text): {"tf_op": path or None, "category": hlo_category or None}}``.  The
op text is the name ``ProfileData`` gives the op's events; the program id
is the number in the name of the ``XLA Modules`` event that holds them
(``jit_serve_step(4502323118771057574)``).
"""
from __future__ import annotations

import struct

DEVICE_PREFIX = "/device:TPU:"


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, start, end):
    """(field number, wire type, value) over one message; a length-delimited
    value is its ``(start, end)`` span in ``buf``."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val = (i, i + n)
            i += n
        elif wire == 1:
            val = buf[i:i + 8]
            i += 8
        elif wire == 5:
            val = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, wire, val


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_value(buf, span):
    """The value span of one map entry (key = 1, value = 2)."""
    for num, _, val in _fields(buf, *span):
        if num == 2:
            return val
    return None


def _stat(buf, span, stat_names):
    """(stat name, value) of one ``XStat``."""
    name = value = None
    for num, wire, val in _fields(buf, *span):
        if num == 1:
            name = stat_names.get(val)
        elif num == 2:
            value = struct.unpack("<d", bytes(val))[0]
        elif num == 3:
            value = val
        elif num == 4:
            value = val - (1 << 64) if val >> 63 else val
        elif num == 5:
            value = _text(buf, val)
        elif num == 7:
            value = stat_names.get(val)
    return name, value


def _plane(buf, span):
    """(name, {(program_id, op text): {"tf_op", "category"}}) of one plane,
    or (name, None) for a plane that is not a TPU device."""
    name, metas, stat_spans = "", [], []
    for num, wire, val in _fields(buf, *span):
        if num == 2:
            name = _text(buf, val)
        elif num == 4:
            metas.append(val)
        elif num == 5:
            stat_spans.append(val)
    if not name.startswith(DEVICE_PREFIX):
        return name, None
    stat_names = {}
    for entry in stat_spans:
        sid, sname = None, ""
        for num, _, val in _fields(buf, *_map_value(buf, entry)):
            if num == 1:
                sid = val
            elif num == 2:
                sname = _text(buf, val)
        stat_names[sid] = sname
    ops = {}
    for entry in metas:
        op, stats = "", {}
        for num, _, val in _fields(buf, *_map_value(buf, entry)):
            if num == 2:
                op = _text(buf, val)
            elif num == 5:
                k, v = _stat(buf, val, stat_names)
                stats[k] = v
        if "program_id" not in stats:
            continue
        key = (stats["program_id"], op)
        rec = {"tf_op": stats.get("tf_op") or None,
               "category": stats.get("hlo_category")}
        # an op can appear twice (its sync and async events); keep the
        # entry that carries a scope path
        if key not in ops or rec["tf_op"]:
            ops[key] = rec
    return name, ops


def read(path) -> dict:
    """``{device plane name: {(program_id, op text): {"tf_op",
    "category"}}}`` of every ``/device:TPU:n`` plane in the file."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for num, wire, val in _fields(buf, 0, len(buf)):
        if num == 1 and wire == 2:
            name, ops = _plane(buf, val)
            if ops is not None:
                out[name] = ops
    return out


def program_id(module_name: str):
    """``jit_serve_step(4502323118771057574)`` -> 4502323118771057574, or
    None for a name without one."""
    _, _, rest = module_name.partition("(")
    digits = rest.rstrip(")")
    return int(digits) if digits.isdigit() else None
