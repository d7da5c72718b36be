"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be imported/run as a fresh process: the first two lines force 512
placeholder host devices before jax initializes.

Per cell:
  1. FULL model, scan-over-layers, lower+compile on the requested mesh
     -> proves the distribution config (sharding, collectives, memory).
  2. (--roofline, single-pod only) 1-period and 2-period *unrolled* variants
     -> cost_analysis of each; linear extrapolation in layer periods gives
     whole-model HLO FLOPs / bytes / collective bytes (XLA's cost analysis
     counts while bodies once — measured, see EXPERIMENTS.md §Dry-run).

Usage:
  python -m repro.launch.dryrun --arch glm4-9b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --out experiments/dryrun
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import cells, get_config, get_shape          # noqa: E402
from repro.core import roofline as RL                           # noqa: E402
from repro.launch import sharding as SH                         # noqa: E402
from repro.launch.mesh import data_shards, make_production_mesh # noqa: E402
from repro.models import RuntimeConfig, build_model             # noqa: E402
from repro.models import modules as M                           # noqa: E402
from repro.models import transformer as T                       # noqa: E402
from repro.optim import OptConfig                               # noqa: E402
from repro.serve.step import make_serve_step                    # noqa: E402
from repro.train.step import make_train_step                    # noqa: E402


def runtime_for(mesh, shape, scan_layers=True, overrides=None):
    rt = RuntimeConfig(
        remat="dots" if shape.kind == "train" else "none",
        moe_groups=data_shards(mesh),
        # production serving default: int8 KV (§Perf A4 — validated to
        # 0.03 max logit error; halves the decode memory floor)
        cache_dtype="int8" if shape.kind == "decode" else "bfloat16",
        scan_layers=scan_layers)
    if overrides:
        rt = dataclasses.replace(rt, **overrides)
    return rt


def reduced_period_cfg(cfg, k: int):
    """cfg with first_dense + k periods of the main group (for extrapolation)."""
    groups = T.plan_groups(cfg)
    main = groups[-1]
    P = len(main.pattern)
    L = cfg.first_dense_layers + k * P
    changes = {"num_layers": L}
    if cfg.encoder_decoder:
        changes["num_encoder_layers"] = k
    return dataclasses.replace(cfg, **changes), groups[-1].repeats


def lower_cell(cfg, shape, mesh, rt, rules=None):
    """Build + lower + compile one cell. Returns (compiled, seconds)."""
    from repro.core import partitioning as PT
    from repro.models.registry import input_specs
    model = build_model(cfg, rt)
    if rules is None:
        rules = SH.TRAIN_RULES if shape.kind != "decode" else SH.DECODE_RULES
        if shape.kind == "decode" and shape.global_batch == 1:
            rules = SH.wide_tp_rules(SH.DECODE_RULES)

    boxed = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pdtype = jnp.float32 if shape.kind == "train" else jnp.dtype(cfg.dtype)
    boxed = jax.tree.map(
        lambda p: M.Param(jax.ShapeDtypeStruct(p.value.shape, pdtype), p.axes),
        boxed, is_leaf=M.is_param)
    params_sds = SH.sds_with_sharding(boxed, mesh, rules)

    bspec = SH.batch_spec(mesh, rules)
    specs = input_specs(cfg, shape, rt)

    def shard_batch(b):
        from repro.core.partitioning import mesh_size
        bsz = mesh_size(bspec[0], mesh) if len(bspec) else 1

        def one(v):
            spec = bspec if (v.ndim and bsz > 1 and v.shape[0] % bsz == 0) \
                else jax.sharding.PartitionSpec()
            return jax.ShapeDtypeStruct(
                v.shape, v.dtype,
                sharding=jax.sharding.NamedSharding(mesh, spec))
        return {k: one(v) for k, v in b.items()}

    t0 = time.time()
    ctx = PT.activation_rules(mesh, rules)
    if shape.kind == "train":
        step_fn, opt = make_train_step(build_model(cfg, rt), OptConfig())
        opt_sds = jax.eval_shape(opt.init, params_sds)
        opt_sds = opt_sds._replace(
            mu=_like(opt_sds.mu, params_sds, mesh, rules, boxed),
            nu=_like(opt_sds.nu, params_sds, mesh, rules, boxed))
        with mesh, ctx:
            lowered = jax.jit(step_fn, donate_argnums=(0, 1)).lower(
                params_sds, opt_sds, shard_batch(specs["batch"]))
    elif shape.kind == "prefill":
        model = build_model(cfg, rt)

        def prefill_fn(params, batch):
            logits, caches = model.prefill(params, batch)
            return jnp.argmax(logits[:, -1:, :], -1), caches
        with mesh, ctx:
            lowered = jax.jit(prefill_fn).lower(
                params_sds, shard_batch(specs["batch"]))
    else:
        serve_fn = make_serve_step(build_model(cfg, rt))
        cache_sh = SH.cache_sharding(specs["caches"], mesh, rules,
                                     shape.global_batch)
        caches_sds = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            specs["caches"], cache_sh)
        with mesh, ctx:
            lowered = jax.jit(serve_fn, donate_argnums=(2,)).lower(
                params_sds, shard_batch(specs["batch"]), caches_sds)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    return compiled, {"lower_s": t_lower, "compile_s": t_compile}


def _like(tree, params_sds, mesh, rules, boxed):
    """Give optimizer-moment SDS the same shardings as their params."""
    shard = SH.shardings_for_tree(boxed, mesh, rules)
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        tree, shard)


def analyze(compiled):
    out = {}
    try:
        ca = RL.cost_analysis(compiled)
        out["flops"] = float(ca.get("flops", 0.0))
        out["bytes"] = float(ca.get("bytes accessed", 0.0))
        out["transcendentals"] = float(ca.get("transcendentals", 0.0))
    except Exception as e:   # pragma: no cover
        out["cost_error"] = str(e)
    try:
        txt = compiled.as_text()
        st = RL.parse_collectives(txt)
        out["collectives"] = {"bytes": st.bytes_by_kind,
                              "counts": st.count_by_kind,
                              "link_bytes": st.link_bytes}
        out["convert_bytes"] = RL.convert_bytes(txt)
        if "bytes" in out:
            # floor at 20%: the adjustment (x1.5 in+out estimate) may
            # overshoot on convert-heavy programs
            out["bytes_adj"] = max(out["bytes"] - out["convert_bytes"],
                                   0.2 * out["bytes"])
        out["hlo_chars"] = len(txt)
    except Exception as e:   # pragma: no cover
        out["hlo_error"] = str(e)
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                out.setdefault("memory", {})[k] = int(v)
    except Exception as e:
        out["memory_error"] = str(e)
    return out


def run_cell(arch, shape_name, mesh_kind, *, do_roofline=True, overrides=None):
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "mesh_shape": dict(mesh.shape), "status": "ok"}
    try:
        rt = runtime_for(mesh, shape, overrides=overrides)
        compiled, times = lower_cell(cfg, shape, mesh, rt)
        rec["times"] = times
        rec["full"] = analyze(compiled)
        del compiled
        if do_roofline and mesh_kind == "single":
            per = {}
            for k in (1, 2):
                cfg_k, repeats = reduced_period_cfg(cfg, k)
                rt_k = runtime_for(mesh, shape, scan_layers=False,
                                   overrides=overrides)
                compiled_k, _ = lower_cell(cfg_k, shape, mesh, rt_k)
                per[k] = analyze(compiled_k)
                per[k]["repeats_full"] = repeats
                del compiled_k
            rec["periods"] = per
            rec["roofline"] = extrapolate(per, cfg, shape, mesh)
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    return rec


def extrapolate(per, cfg, shape, mesh):
    """cost(R) = cost(1p) + (R-1) * (cost(2p) - cost(1p))."""
    c1, c2 = per[1], per[2]
    R = c1["repeats_full"]
    out = {}
    for key in ("flops", "bytes", "bytes_adj"):
        if key in c1 and key in c2:
            out[key] = c1[key] + (R - 1) * (c2[key] - c1[key])
    cb1 = c1.get("collectives", {}).get("link_bytes", 0.0)
    cb2 = c2.get("collectives", {}).get("link_bytes", 0.0)
    out["collective_link_bytes"] = cb1 + (R - 1) * (cb2 - cb1)
    chips = 1
    for v in mesh.shape.values():
        chips *= v
    terms = RL.RooflineTerms(
        flops=out.get("flops", 0.0), bytes_accessed=out.get("bytes", 0.0),
        collective_link_bytes=out["collective_link_bytes"], chips=chips,
        model_flops=RL.model_flops_for(cfg, shape))
    out.update(terms.as_dict())
    if "bytes_adj" in out:
        out["t_memory_adj_s"] = out["bytes_adj"] / RL.HBM_BW
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--no-roofline", action="store_true")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    todo = []
    if args.all:
        for arch, sname, skip in cells():
            for mesh_kind in ("single", "multi"):
                todo.append((arch, sname, mesh_kind))
    else:
        todo.append((args.arch, args.shape, args.mesh))

    failed = 0
    for arch, sname, mesh_kind in todo:
        path = os.path.join(args.out, f"{arch}__{sname}__{mesh_kind}.json")
        if args.all and os.path.exists(path):
            continue
        t0 = time.time()
        rec = run_cell(arch, sname, mesh_kind,
                       do_roofline=not args.no_roofline)
        rec["wall_s"] = time.time() - t0
        with open(path, "w") as f:
            json.dump(rec, f, indent=1, default=str)
        print(f"[{rec['status']:5s}] {arch} {sname} {mesh_kind} "
              f"({rec['wall_s']:.0f}s)", flush=True)
        if rec["status"] == "error":
            failed += 1
            print(rec["error"], flush=True)
    if failed:
        raise SystemExit(f"{failed} of {len(todo)} cells failed")


if __name__ == "__main__":
    main()
