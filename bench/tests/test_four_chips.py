"""The harness on four chips, rehearsed on four virtual CPU devices (in a
subprocess: the device count is fixed before JAX starts): the toy cell at
``chips`` 4 with its engine tensor-parallel over them (``tp`` 4, exact
mode; 4 query heads over 2 KV heads, GLM-4's replicated-KV case) is
correct, the fp8 control is not, the engine's weights are made where its
plan keeps them, and the check's weights equal one device's.  A cell whose
chips and engine disagree ends the run, and the roofline and MFU of a step
read against every chip's peaks."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

import run
import tiny

SCRIPT = """
    import json, sys
    sys.path[:0] = {paths!r}
    import jax, numpy as np
    from jax.sharding import NamedSharding
    import repro.serve
    import run, tiny, weights
    from repro.dist import tp as TP

    out = {{}}
    devs = jax.devices()
    # the check's weights: sharded on each leaf's largest axis, equal bit
    # for bit to one device's
    one = weights.make(tiny.MODEL, 5)
    spread = weights.make(tiny.MODEL, 5, shardings=run.spread_over(devs))
    out["spread_equal"] = all(np.array_equal(np.asarray(one[k]),
                                             np.asarray(spread[k]))
                              for k in one)
    out["spread_sharded"] = sorted(
        k for k, a in spread.items()
        if a.addressable_shards[0].data.shape != a.shape)

    made = {{}}
    build_engine = repro.serve.build_engine

    def spy(cfg, ecfg, params):
        made["params"] = params
        return build_engine(cfg, ecfg, params=params)
    repro.serve.build_engine = spy

    def placed(engine):
        plan = engine._tpx.plan
        specs = TP.param_specs(engine.model, engine.params, plan)
        want = jax.tree.map(lambda _, s: NamedSharding(plan.mesh, s),
                            engine.params, specs)
        leaves = jax.tree.leaves(engine.params)
        out["leaves"] = len(leaves)
        out["planned"] = sum(a.sharding == s for a, s in zip(
            leaves, jax.tree.leaves(want)))
        out["sharded"] = sum(a.addressable_shards[0].data.shape != a.shape
                             for a in leaves)
        out["unmoved"] = sum(a is b for a, b in zip(
            leaves, jax.tree.leaves(made["params"])))
        out["mesh"] = len(plan.mesh.devices.flat)

    res = run.run(tiny.args(seed=424242424242), tiny.spec(chips=4),
                  hooks={{"engine": placed}})
    out["correct"] = res["correct"]
    out["count"] = res["device"]["count"]
    out["per_chip"] = len(res["device"]["memory_peak_bytes_per_chip"])
    ctl = run.run(tiny.args(seed=424242424242, control=1),
                  tiny.spec(tiny.CLOSED, chips=4))
    out["control_correct"] = ctl["correct"]
    out["control_gap"] = ctl["check"]["max_logit_gap"]
    print(json.dumps(out))
"""


def test_four_chip_cell_on_virtual_devices():
    paths = [str(run.ROOT / "src"), str(run.BENCH),
             str(run.BENCH / "tests")]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(SCRIPT.format(paths=paths))],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["spread_equal"]
    assert set(out["spread_sharded"]) >= {"embed", "wq", "w_gate", "w_down",
                                          "lm_head"}
    assert out["mesh"] == 4
    # every leaf where the plan puts it, made there: shard_params kept them
    assert out["planned"] == out["unmoved"] == out["leaves"]
    assert out["sharded"] > 0
    assert out["correct"] is True
    assert out["count"] == out["per_chip"] == 4
    assert out["control_correct"] is False
    assert out["control_gap"]["value"] > out["control_gap"]["limit"]


@pytest.mark.parametrize("chips,tp", [(4, 1), (1, 4), (4, 2)])
def test_chips_and_engine_tp_that_disagree_end_the_run(chips, tp):
    spec = tiny.spec()
    spec["cell"]["chips"] = chips
    spec["traffic"]["engine"]["tp"] = tp
    with pytest.raises(SystemExit, match="they have to agree"):
        run.run(tiny.args(), spec)


def test_a_step_on_four_chips_reads_against_four_chips_peaks():
    """The same need and the same per-chip step time read a quarter of
    the one-chip roofline share and MFU."""
    readers = [run.metric_reader(n) for n in ("decode_step_roofline",
                                              "decode_mfu")]
    info = {"trace": {"programs": {"decode": {"count": 10,
                                              "seconds": 0.4}}},
            "need": {"flops": 3e12, "bytes": 8e10, "steps": 10},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "chips": 1}
    for read in readers:
        one = read(info)
        assert one > 0
        assert read(dict(info, chips=4)) == pytest.approx(one / 4)
    # the roofline share by hand: bytes bound, 8e9 B / 819 GB/s over 40 ms
    assert readers[0](info) == pytest.approx(100 * 8e9 / 819e9 / 0.04)
