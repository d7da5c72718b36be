"""The harness end to end on the CPU at a toy size: a sound run is
correct, the fp8 control and each fault of the served path are not, a
per-layer metric with nothing to read ends the run, and a run without a
TPU, or without the program, prints no result."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import run
import tiny

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen05b.chat",
         "--seed", "3", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = cli(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no fallback" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for extra in ((), ("--platform", "cpu")):
        p = cli(tmp_path, *extra)
        assert p.returncode != 0
        assert p.stdout.strip() == ""


def test_sound_run_is_correct():
    out = run.run(tiny.args(), tiny.spec())
    assert out["correct"] is True
    assert out["check"]["max_logit_gap"]["value"] <= tiny.spec()[
        "limits"]["max_logit_gap"]
    assert list(out)[-1] == "check"
    assert out["device"]["platform"] == "cpu"
    assert set(out["metrics"]) >= {"setup_s", "ttft_p50_s", "ttft_p95_s",
                                   "output_tok_per_s"}
    assert out["attempted"] > 0 and out["failed"] == 0
    json.dumps(out)


@pytest.mark.parametrize("traffic", [tiny.OPEN, tiny.CLOSED])
def test_control_in_the_programs_place_is_not_correct(traffic):
    """The fp8 control judged by the run's own comparison fails it."""
    out = run.run(tiny.args(control=1), tiny.spec(traffic))
    assert out["correct"] is False
    gap = out["check"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_a_metric_whose_source_is_missing_ends_the_run():
    """A trace whose jitted module names match nothing in programs.json
    (a renamed step) gives no decode time: the run fails, it does not
    leave the metric out."""
    import trace_reduce as TR
    red = TR.reduce_trace(BENCH / "tests" / "data" / "small.xplane.pb",
                          table={"jit_renamed_step": "decode"})
    info = {"trace": red, "need": {"flops": 1.0, "bytes": 1.0, "steps": 1},
            "peaks": {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0},
            "chips": 1}
    defs = [{"name": "decode_step_ms", "unit": "ms"}]
    with pytest.raises(SystemExit, match="decode_step_ms"):
        run.read_per_layer(defs, info)
    found = TR.reduce_trace(BENCH / "tests" / "data" / "small.xplane.pb")
    assert run.read_per_layer(defs, dict(info, trace=found))[
        "decode_step_ms"]["value"] > 0


def test_closed_loop_runs_and_is_correct():
    out = run.run(tiny.args(seed=7), tiny.spec(tiny.CLOSED))
    assert out["correct"] is True
    assert "ttft_p95_s" not in out["metrics"] or out["attempted"] > 0


def _token_altered(engine):
    step = engine.serve_step

    def altered(params, batch, caches):
        tok, caches = step(params, batch, caches)
        return (tok + 1) % engine.model.cfg.vocab_size, caches
    engine.serve_step = altered


def _state_unchanged(engine):
    import jax
    inner = engine.serve_step.__wrapped__
    engine.serve_step = jax.jit(
        lambda params, batch, caches: (inner(params, batch, caches)[0],
                                       caches))


def _half_batch_left_out(engine):
    step = engine.serve_step

    def half(params, batch, caches):
        tok, caches = step(params, batch, caches)
        B = tok.shape[0]
        # the second half of the rows is not computed: it repeats the
        # first half's tokens
        return tok.at[B // 2:].set(tok[:B // 2]), caches
    engine.serve_step = half


# at the toy rate the open loop seldom fills the second half of the
# slots, so the half-batch fault is driven by the closed loop, which does
@pytest.mark.parametrize("fault,traffic", [
    (_token_altered, tiny.OPEN), (_token_altered, tiny.CLOSED),
    (_state_unchanged, tiny.OPEN), (_state_unchanged, tiny.CLOSED),
    (_half_batch_left_out, tiny.CLOSED)])
def test_faults_of_the_timed_path_are_not_correct(fault, traffic):
    out = run.run(tiny.args(seed=11), tiny.spec(traffic),
                  hooks={"engine": fault})
    assert out["correct"] is False
