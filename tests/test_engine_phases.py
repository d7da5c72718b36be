"""Engine phases and model scopes: the engine's own observability.

Each phase of an engine cycle is one ``repro.obs.tracer.Phase``: an
``engine.<phase>`` profiler annotation, the ``DispatchProfiler`` phase and
the ``Tracer`` span, from one pair of stamps.  The model's
``jax.named_scope``s reach the op metadata of the compiled steps."""
import gc

import jax
import numpy as np
import pytest

from repro import obs
from repro.obs import tracer as T
from repro.serve.scheduler import Request

from test_obs import make_engine, setup


class Recorder:
    """Stands in for ``TraceAnnotation`` / ``StepTraceAnnotation``: logs
    every enter and exit as ("+"/"-", name, kwargs)."""
    log = []

    def __init__(self, name, **kw):
        self.name, self.kw = name, kw

    def __enter__(self):
        Recorder.log.append(("+", self.name, self.kw))
        return self

    def __exit__(self, *exc):
        Recorder.log.append(("-", self.name, self.kw))
        return False


@pytest.fixture
def recorded(monkeypatch):
    Recorder.log = []
    monkeypatch.setattr(T, "TraceAnnotation", Recorder)
    monkeypatch.setattr(T, "StepTraceAnnotation", Recorder)
    return Recorder.log


def nesting(log):
    """Check every exit closes the innermost open phase; return the
    cycles as (step kwargs, [(depth, name) of the phases inside])."""
    stack, cycles = [], []
    for kind, name, kw in log:
        if kind == "+":
            if name == "engine.step":
                assert not stack, stack
                cycles.append((kw, []))
            elif stack:
                cycles[-1][1].append((len(stack), name))
            stack.append(name)
        else:
            assert stack and stack[-1] == name, (stack, name)
            stack.pop()
    assert not stack
    return cycles


def run_engine(tracer=None, **kw):
    _, model, params = setup()
    eng = make_engine(model, params, tracer=tracer, **kw)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(1, 100, n).astype(np.int32),
                    max_new_tokens=4)
            for i, n in enumerate((21, 9, 40, 13))]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    assert all(r.done for r in reqs)
    return eng


def test_chunked_cycle_phases_nest(recorded):
    tr = obs.Tracer()
    eng = run_engine(tracer=tr)
    cycles = nesting([e for e in recorded if e[1] != "engine.gc"])
    assert len(cycles) == eng.steps
    assert [kw["step_num"] for kw, _ in cycles] == list(range(eng.steps))
    # a cycle that consumes a decode, admits, runs a slab and submits the
    # next decode does so in that order, each phase directly in the step
    order = ("engine.consume", "engine.admit", "engine.chunk",
             "engine.decode_submit")
    full = 0
    for _, inner in cycles:
        top = [name for depth, name in inner if depth == 1]
        if all(name in top for name in order):
            full += 1
            first = [top.index(name) for name in order[:1]] + \
                [top.index(name, top.index("engine.consume"))
                 for name in order[1:]]
            assert first == sorted(first), top
    assert full
    names = {name for _, inner in cycles for _, name in inner}
    assert {"engine.stream_out", "engine.first_token",
            "engine.finish"} <= names
    # stream-out is the consume phase's blocking read; finish sits in the
    # phase whose tokens ended the request
    for _, inner in cycles:
        for i, (depth, name) in enumerate(inner):
            if name == "engine.stream_out":
                assert inner[i - 1] == (depth - 1, "engine.consume")
    # the tracer keeps its span names, from the same stamps
    c = tr.counts()
    assert c["device_submit"] == c["stream_out"] == c["decode"] \
        == eng.decode_steps
    assert c["chunk"] == eng.chunk_calls
    sub, out = tr.events("device_submit")[0], tr.events("stream_out")[0]
    assert sub[0] + sub[5] <= out[0]
    dec = tr.events("decode")[0]
    assert dec[0] == sub[0] and dec[0] + dec[5] == \
        pytest.approx(out[0] + out[5])


def test_bucketed_cycle_phases(recorded):
    _, model, params = setup()
    from repro.serve import EngineConfig
    from repro.serve.scheduler import ServingEngine
    from repro.serve.step import make_prefill_step, make_serve_step
    tr = obs.Tracer()
    eng = ServingEngine(model, prefill_step=make_prefill_step(model),
                        serve_step=make_serve_step(model), params=params,
                        tracer=tr, config=EngineConfig(slots=2, cache_len=64))
    for i, n in enumerate((5, 11, 7)):
        eng.submit(Request(rid=i, prompt=np.arange(1, n + 1, dtype=np.int32),
                           max_new_tokens=3))
    eng.run_until_drained()
    cycles = nesting([e for e in recorded if e[1] != "engine.gc"])
    names = [name for _, inner in cycles for _, name in inner]
    assert names.count("engine.prefill") == eng.prefill_calls \
        == len(tr.events("prefill"))
    assert "engine.admit" in names and "engine.decode_submit" in names


def test_phase_feeds_profiler_and_tracer(recorded):
    tr = obs.Tracer()
    prof = obs.DispatchProfiler()
    with T.Phase("chunk", profiler=prof, prof="chunk_prefill", tracer=tr,
                 span="chunk", track=2, rid=7, off=0) as ph:
        pass
    assert ph.t0 <= ph.t1
    assert [e[:2] for e in recorded] == [("+", "engine.chunk"),
                                         ("-", "engine.chunk")]
    (row,) = prof.phase_rows()
    assert row["phase"] == "chunk_prefill" and row["occurrences"] == 1
    ((ts, ph_kind, name, track, rid, dur, args),) = tr.events()
    assert (ph_kind, name, track, rid, args) == ("X", "chunk", 2, 7,
                                                 {"off": 0})
    assert ts == pytest.approx(tr.rel(ph.t0)) and \
        dur == pytest.approx(ph.t1 - ph.t0)
    # no sinks: the annotation alone; no span name: no tracer event
    with T.Phase("step", step=3, tracer=tr):
        pass
    assert recorded[-2] == ("+", "engine.step", {"step_num": 3})
    assert len(tr.events()) == 1


def test_phase_closes_on_error(recorded):
    prof = obs.DispatchProfiler()
    with pytest.raises(RuntimeError):
        with T.Phase("admit", profiler=prof, prof="admit"):
            raise RuntimeError("boom")
    assert recorded[-1][:2] == ("-", "engine.admit")
    assert prof.phase_rows()[0]["occurrences"] == 1


def test_gc_annotation(recorded):
    T.annotate_gc()
    T.annotate_gc()
    assert gc.callbacks.count(T._gc_phase) == 1
    gc.collect()
    gcs = [e for e in recorded if e[1] == "engine.gc"]
    assert gcs[0] == ("+", "engine.gc", {"generation": 2})
    assert gcs[-1][0] == "-" and len(gcs) % 2 == 0


@pytest.mark.parametrize("entry", ["decode", "chunk"])
def test_compiled_steps_carry_model_scopes(entry):
    """The scopes reach the op metadata of the engine's jitted steps."""
    _, model, params = setup()
    eng = make_engine(model, params)
    if entry == "decode":
        batch = {"tokens": jax.numpy.zeros((eng.slots, 1), np.int32),
                 "pos": jax.numpy.zeros((eng.slots,), np.int32),
                 "sample_nonce": jax.numpy.zeros((eng.slots,), np.int32),
                 "block_tables": eng._decode_block_tables()}
        step = eng.serve_step
    else:
        C = eng.chunk_size
        batch = {"tokens": jax.numpy.zeros((1, C), np.int32),
                 "offset": jax.numpy.zeros((1,), np.int32),
                 "valid": jax.numpy.full((1,), C, np.int32),
                 "stage_base": jax.numpy.zeros((1,), np.int32),
                 "block_tables": jax.numpy.asarray(
                     eng.backend.block_tables[:1])}
        step = eng.chunk_step
    # the compiled module's op_name metadata is what a device profile
    # carries as each op's ``tf_op`` path
    text = step.lower(eng.params, batch, eng.caches).compile().as_text()
    for scope in ("embed", "attention/qkv", "attention/kv_write",
                  "attention/kv_gather", "attention/attend",
                  "attention/out_proj", "mlp", "readout", "sample"):
        assert f"/{scope}/" in text, scope
