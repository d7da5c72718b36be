"""xplane_meta.py: op metadata read from the wire format, checked on the
small v5e trace of ``test_trace_reduce.py`` against numbers read from the
same file with tsl's own ``xplane_pb2``, and on hand-built messages."""
import struct
from pathlib import Path

import pytest

import trace_scopes as TS
import xplane_meta as XM

DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"
GATHER = "jit(serve_step)/while/body/closed_call/gather"


@pytest.fixture(scope="module")
def meta():
    return XM.read(DATA)


@pytest.fixture(scope="module")
def device_ops(meta):
    """(inclusive ns, self ns, metadata) of every ``XLA Ops`` event of the
    device, joined through its module's program id."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(DATA))
    plane = [p for p in data.planes if p.name == "/device:TPU:0"][0]
    lines = {line.name: line for line in plane.lines}
    mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for e in lines["XLA Modules"].events)
    evs = list(lines["XLA Ops"].events)
    own = TS.self_times([(e.start_ns, e.start_ns + e.duration_ns)
                         for e in evs])
    out = []
    for ev, self_ns in zip(evs, own):
        mod = TS._module(mods, ev.start_ns)
        rec = meta["/device:TPU:0"][(XM.program_id(mod[2]), ev.name)]
        out.append((ev.duration_ns, self_ns, ev.name, rec))
    return out


def test_every_event_metadata_entry_is_walked():
    buf = memoryview(DATA.read_bytes())
    for num, _, span in XM._fields(buf, 0, len(buf)):
        if num != 1:
            continue
        fields = list(XM._fields(buf, *span))
        name = next(XM._text(buf, v) for n, _, v in fields if n == 2)
        if name == "/device:TPU:0":
            assert sum(n == 4 for n, _, _ in fields) == 348
            return
    raise AssertionError("no /device:TPU:0 plane")


def test_only_device_planes(meta):
    assert list(meta) == ["/device:TPU:0"]


def test_copy_start_has_no_scope_path(meta):
    recs = [rec for (pid, op), rec in meta["/device:TPU:0"].items()
            if op.startswith("%copy-start.15 ")]
    assert len(recs) == 2          # one per program
    assert all(rec["tf_op"] is None and rec["category"] == "copy-start"
               for rec in recs)


def test_scoped_op_time(device_ops):
    ns = sum(d for d, _, _, rec in device_ops
             if (rec["tf_op"] or "").startswith(GATHER))
    assert ns / 1e6 == pytest.approx(0.0991, rel=0.01)


def test_ops_without_a_path_in_self_time(device_ops):
    untagged = [(d, s, name) for d, s, name, rec in device_ops
                if rec["tf_op"] is None]
    whiles = [(d, s) for d, s, name in untagged if name.startswith("%while")]
    assert sum(d for d, _, _ in untagged) / 1e6 == \
        pytest.approx(0.878, rel=0.01)
    assert sum(d for d, _ in whiles) / 1e6 == pytest.approx(0.759, rel=0.01)
    # self time keeps a layer scan's own overhead only
    while_self = sum(s for _, s in whiles)
    assert 0 < while_self < 0.1 * sum(d for d, _ in whiles)
    assert sum(s for _, s, _ in untagged) == pytest.approx(
        sum(d for d, _, _ in untagged) - sum(d for d, _ in whiles)
        + while_self)


def test_program_id():
    assert XM.program_id("jit_serve_step(4502323118771057574)") == \
        4502323118771057574
    assert XM.program_id("jit_serve_step") is None


# ------------------------------------------------------------ wire format
def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        if n:
            out += bytes([b | 0x80])
        else:
            return out + bytes([b])


def _field(num, payload=None, varint=None, fixed64=None):
    if varint is not None:
        return _varint(num << 3) + _varint(varint)
    if fixed64 is not None:
        return _varint(num << 3 | 1) + fixed64
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _entry(key, value):
    return _field(1, varint=key) + _field(2, value)


def _space(plane_name, stat_names, ops):
    """An ``XSpace`` of one plane: ``stat_names`` {id: name}; ``ops``
    [(op text, [(stat id, wire field, value)])]."""
    plane = _field(2, plane_name.encode())
    plane += _field(3, _field(2, b"XLA Ops") + _field(4, b"\x08\x01"))
    for sid, name in stat_names.items():
        plane += _field(5, _entry(sid, _field(1, varint=sid)
                                  + _field(2, name.encode())))
    for i, (text, stats) in enumerate(ops, 1):
        body = _field(1, varint=i) + _field(2, text.encode())
        for sid, num, value in stats:
            stat = _field(1, varint=sid)
            if num == 2:
                stat += _field(2, fixed64=struct.pack("<d", value))
            elif num == 5:
                stat += _field(5, value.encode())
            else:
                stat += _field(num, varint=value)
            body += _field(5, stat)
        plane += _field(4, _entry(i, body))
    return _field(1, plane)


def test_hand_built_space(tmp_path):
    names = {1: "program_id", 2: "tf_op", 3: "hlo_category", 4: "flops",
             5: "loop fusion", 6: "offset"}
    ops = [("%fusion.1 = f32[2]", [(1, 3, 2**63 + 5), (2, 5, "jit(f)/mlp/dot"),
                                   (3, 7, 5), (4, 2, 1.5)]),
           ("%copy.2 = f32[2]", [(1, 3, 7), (6, 4, 2**64 - 3)]),
           ("%param = f32[2]", [(4, 4, 1)])]
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_space("/device:TPU:3", names, ops)
                     + _space("/host:CPU", names, ops))
    assert XM.read(path) == {"/device:TPU:3": {
        (2**63 + 5, "%fusion.1 = f32[2]"): {"tf_op": "jit(f)/mlp/dot",
                                            "category": "loop fusion"},
        (7, "%copy.2 = f32[2]"): {"tf_op": None, "category": None}}}
    buf = memoryview(_field(1, varint=6) + _field(4, varint=2**64 - 3))
    assert XM._stat(buf, (0, len(buf)), names) == ("offset", -3)
