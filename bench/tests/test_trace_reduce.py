"""trace_reduce.py on a small trace recorded on a v5e: the toy cell of
``tiny.py`` in a closed loop, traced inside a ``bench.window`` span that
also holds a 20 ms host sleep (``bench.idle``)."""
from pathlib import Path

import pytest

import trace_reduce as TR

DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return TR.reduce_trace(DATA)


def test_known_numbers(reduced):
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(0.108346132, abs=1e-9)
    assert reduced["busy_s"] == pytest.approx(0.000941243, abs=1e-9)
    assert reduced["programs"] == {
        name: {"count": c, "seconds": pytest.approx(s, abs=1e-9)}
        for name, c, s in (("chunk", 9, 0.00031603), ("decode", 22, 0.000634894))}


def test_busy_and_programs_agree_with_a_plain_count(reduced):
    """Recount from the raw events: the device's op intervals merged by
    a sweep, the modules by their names."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(DATA))
    host = [e for p in data.planes if p.name.startswith("/host:CPU")
            for line in p.lines for e in line.events
            if e.name == "bench.window"]
    t0, t1 = host[0].start_ns, host[0].start_ns + host[0].duration_ns
    dev = [p for p in data.planes if p.name == "/device:TPU:0"][0]
    spans, mods = [], {}
    for line in dev.lines:
        for e in line.events:
            s, t = max(e.start_ns, t0), min(e.start_ns + e.duration_ns, t1)
            if line.name in ("XLA Ops", "Async XLA Ops") and t > s:
                spans.append((s, t))
            if line.name == "XLA Modules" and e.start_ns >= t0 and \
                    e.start_ns + e.duration_ns <= t1:
                mods[e.name.split("(")[0]] = mods.get(
                    e.name.split("(")[0], 0) + 1
    busy, end = 0.0, None
    for s, t in sorted(spans):
        if end is None or s > end:
            busy += t - s
            end = t
        elif t > end:
            busy += t - end
            end = t
    assert reduced["busy_s"] == pytest.approx(busy / 1e9, rel=1e-9)
    table = TR.programs_table()
    for module, count in mods.items():
        if module in table:
            assert reduced["programs"][table[module]]["count"] == count
    # the 20 ms host sleep is an idle gap named by its span
    names = [name for name, _ in reduced["idle_gaps"]]
    assert names[0] == "bench.idle"
    assert reduced["idle_gaps"][0][1] == pytest.approx(0.0229, abs=1e-4)


def test_top_ops_are_named_by_program(reduced):
    assert len(reduced["top_ops"]) == 10
    assert all(name.split(":")[0] in ("decode", "chunk", "copy_on_write",
                                      "none") or "jit_" in name
               for name, _ in reduced["top_ops"])
    secs = [s for _, s in reduced["top_ops"]]
    assert secs == sorted(secs, reverse=True)


def test_op_name_drops_layout():
    assert TR.op_name("%fusion.3 = bf16[8,128]{1,0:T(8,128)} fusion(x)") \
        == "fusion.3 bf16[8,128]"
    assert TR.op_name("%while.5 = (s32[]{:T(128)}, bf16[2]) while(x)") \
        == "while.5 s32[]"


def test_a_trace_without_the_window_span_raises(tmp_path):
    with pytest.raises(Exception):
        TR.reduce_trace(tmp_path / "missing.xplane.pb")
