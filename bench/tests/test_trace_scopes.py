"""trace_scopes.py: self time, scopes and idle by host phase.

Hand-built intervals check the arithmetic.  Two traces recorded on a v5e
from the toy cell of ``tiny.py`` check the reduction: ``small.xplane.pb``
from a program without phases or scopes, ``scoped.xplane.pb`` from one
with both (the engine's ``engine.*`` phases, the model's named scopes),
each traced inside a ``bench.window`` that holds a 20 ms ``bench.idle``
sleep."""
from pathlib import Path

import pytest

import trace_reduce as TR
import trace_scopes as TS

DATA = Path(__file__).resolve().parent / "data"


def test_self_times_of_nested_intervals():
    ivs = [(0, 100), (10, 30), (12, 20), (40, 60), (100, 110), (40, 45),
           (55, 60)]
    assert TS.self_times(ivs) == [100 - 20 - 20, 20 - 8, 8, 20 - 5 - 5,
                                  10, 5, 5]
    assert TS.self_times([]) == []


@pytest.mark.parametrize("tf_op, scope", [
    ("jit(serve_step)/while/body/closed_call/attention/kv_gather/gather",
     "attention"),
    ("jit(serve_step)/while/body/closed_call/mlp/dot_general", "mlp"),
    ("jit(serve_step)/embed/convert_element_type", "embed"),
    ("jit(serve_step)/readout/dot_general", "readout"),
    ("jit(serve_step)/sample/reduce", "sample"),
    ("jit(serve_step)/while/body/closed_call/add", "other"),
    (None, "unattributed"),
    ("", "unattributed"),
])
def test_scope_of(tf_op, scope):
    assert TS.scope_of(tf_op) == scope


def test_innermost_span():
    spans = [(10, 90, "engine.step"), (20, 40, "engine.consume"),
             (25, 30, "engine.stream_out"), (50, 60, "engine.admit"),
             (95, 120, "bench.idle")]
    assert TS.innermost(spans, 0, 100) == [
        (0, 10, "bench.none"), (10, 20, "engine.step"),
        (20, 25, "engine.consume"), (25, 30, "engine.stream_out"),
        (30, 40, "engine.consume"), (40, 50, "engine.step"),
        (50, 60, "engine.admit"), (60, 90, "engine.step"),
        (90, 95, "bench.none"), (95, 100, "bench.idle")]
    parts, _ = TS._split((15, 55), TS.innermost(spans, 0, 100), 0)
    assert dict(parts) == {"engine.step": 15, "engine.consume": 15,
                           "engine.stream_out": 5, "engine.admit": 5}


@pytest.fixture(scope="module", params=["small", "scoped"])
def trace(request):
    path = DATA / f"{request.param}.xplane.pb"
    return request.param, TR.reduce_trace(path), TS.reduce_scopes(path)


def test_idle_by_span_sums_to_the_idle_time(trace):
    _, base, red = trace
    idle = base["window_s"] - base["busy_s"]
    assert sum(red["phase_idle"].values()) == pytest.approx(idle, rel=1e-6)
    assert red["idle_gaps"][0][0] == "bench.idle"
    assert red["idle_gaps"][0][1] == pytest.approx(
        base["idle_gaps"][0][1], rel=1e-9)


def test_program_parts_sum_to_its_device_time(trace):
    """Scoped, ``other`` and ``unattributed`` self time make up a
    program's device time but for the gaps between its ops, which at this
    toy size (ops of about a microsecond) are 1.3 % of a decode step and
    4.6 % of a chunk."""
    name, base, red = trace
    for prog, most in (("decode", 0.02), ("chunk", 0.05)):
        parts = dict(red["scopes"][prog])
        total = base["programs"][prog]["seconds"]
        assert sum(parts.values()) == pytest.approx(total, rel=1e-6)
        assert 0 <= parts.pop("between_ops") <= most * total


def test_top_ops_in_self_time(trace):
    _, base, red = trace
    secs = [s for _, s in red["top_ops"]]
    assert secs == sorted(secs, reverse=True)
    # the layer scan's body is no longer counted again under the scan
    assert not any(":while." in name for name, _ in red["top_ops"])
    assert any(":while." in name for name, _ in base["top_ops"])


def test_an_unscoped_program_has_no_scopes():
    red = TS.reduce_scopes(DATA / "small.xplane.pb")
    assert set(red["scopes"]["decode"]) == {"other", "unattributed",
                                            "between_ops"}
    assert all(k.startswith("bench.") for k in red["phase_idle"])


def test_scoped_trace_names_the_step_and_the_gaps():
    red = TS.reduce_scopes(DATA / "scoped.xplane.pb", top=1000)
    decode = red["scopes"]["decode"]
    assert {"embed", "attention", "mlp", "readout"} <= set(decode)
    # the decode step's argmax is fused into the LM head's ops (readout);
    # the chunk step's runs on its own
    assert "sample" in red["scopes"]["chunk"]
    assert decode["attention"] > decode["other"]
    assert all(name.split(":")[1] in TS.SCOPES + ("other", "unattributed")
               for name, _ in red["top_ops"] if not name.startswith("none:"))
    assert any(k.startswith("engine.") for k in red["phase_idle"])
    for name, secs in red["idle_gaps"]:
        if secs > 1e-3:
            assert name.startswith("engine.") or name == "bench.idle", \
                (name, secs)
