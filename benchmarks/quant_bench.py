"""Quantization benchmark -> table + BENCH_quant.json.

Quantization moves the roofline itself (DESIGN.md §5): at OI ~= 1 the
bound is bytes/BW, so the headline numbers here are *modeled bytes* ratios
(exact, from the registry's audited cost models — int8/int4 values + scale
traffic vs the bf16 stream) next to measured interpret-mode wall times and
fraction-of-roofline, plus the accuracy cost vs the fp32 oracle:

  * qgemv int8 / int4  vs gemv bf16      (the decode projection GEMV)
  * mx_qgemv mx4 / fp8 + fused swiglu + grouped expert dispatch
    (MX microscaling, DESIGN.md §11: fp4/fp8 values + E8M0 exponents)
  * paged_decode_attention_int8 vs bf16  (the paged decode cache stream)
  * qwen2-moe engines bf16 / int8 / mx4 tok/s, modeled joules/token,
    and the byte-exact quantized-MoE decode-step dispatch audit

Acceptance self-checks (raise on violation): qgemv-int8 modeled bytes
<= 0.6x the bf16 gemv bytes at the same shape, mx4 <= 0.28x and
fp8 <= 0.55x, int8 outputs within rtol ~2e-2 of the fp32 oracle (int4
documented at ~2e-1, mx4 ~0.35, fp8 ~0.1), modeled joules/token strictly
falling mx4 < int8 < bf16, and the mx4/fp8 MoE audits must match.

    PYTHONPATH=src python benchmarks/quant_bench.py --fast

Interpret-mode wall times on CPU are NOT TPU performance (DESIGN.md §3);
the modeled-bytes ratios are exact on any backend.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

INT8_RTOL = 2e-2       # documented tolerance vs the fp32 oracle
INT4_RTOL = 2e-1


def _measure(fn, iters):
    import jax
    out = fn()
    jax.tree.map(lambda x: x.block_until_ready(), out)
    best = float("inf")
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        out = fn()
        jax.tree.map(lambda x: x.block_until_ready(), out)
        best = min(best, time.perf_counter() - t0)
    return best


def bench_qgemv(*, N, K, iters):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import repro.kernels as Kn
    from repro.kernels import ref as R
    from repro.quant import quantize
    from repro.tune import REGISTRY
    from repro.tune.cache import get_tuned
    from repro.tune.search import roofline_time

    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    w = jax.random.normal(ks[0], (N, K), jnp.float32)
    x = jax.random.normal(ks[1], (K,), jnp.bfloat16)
    oracle = np.asarray(R.gemv(w, x.astype(jnp.float32)))
    scale = float(np.max(np.abs(oracle)))

    wb = w.astype(jnp.bfloat16)
    spec_bf = REGISTRY["gemv"]
    bf_bytes = spec_bf.bytes(wb, x)
    cfg = get_tuned("gemv", wb, x)
    t_bf = _measure(lambda: Kn.gemv(wb, x, cfg), iters)
    rows = [{
        "kernel": "gemv", "dtype": "bfloat16", "shape": f"N={N} K={K}",
        "modeled_bytes": bf_bytes, "bytes_ratio_vs_bf16": 1.0,
        "measured_us": t_bf * 1e6,
        "roofline_us": roofline_time(spec_bf, (wb, x)) * 1e6,
        "fraction_of_roofline": roofline_time(spec_bf, (wb, x)) / t_bf,
        "max_rel_err_vs_fp32": float(
            np.max(np.abs(np.asarray(Kn.gemv(wb, x, cfg)) - oracle))
            / scale),
    }]
    spec_q = REGISTRY["qgemv"]
    for bits, rtol in ((8, INT8_RTOL), (4, INT4_RTOL)):
        qt = quantize(w, bits=bits, group_size=128, axis=-1)
        args = (qt.values, qt.scales, x)
        q_bytes = spec_q.bytes(*args)
        qcfg = get_tuned("qgemv", *args, variant_kwargs={"bits": bits})
        t = _measure(lambda: Kn.qgemv(*args, qcfg, bits=bits), iters)
        y = np.asarray(Kn.qgemv(*args, qcfg, bits=bits))
        err = float(np.max(np.abs(y - oracle)) / scale)
        ratio = q_bytes / bf_bytes
        rows.append({
            "kernel": "qgemv", "dtype": f"int{bits}",
            "shape": f"N={N} K={K} g=128",
            "modeled_bytes": q_bytes, "bytes_ratio_vs_bf16": ratio,
            "measured_us": t * 1e6,
            "roofline_us": roofline_time(spec_q, args) * 1e6,
            "fraction_of_roofline": roofline_time(spec_q, args) / t,
            "max_rel_err_vs_fp32": err,
            "speedup_vs_bf16": t_bf / t,
        })
        if bits == 8:
            assert ratio <= 0.6, \
                f"qgemv int8 modeled bytes {ratio:.3f}x bf16 (want <= 0.6)"
            assert err <= INT8_RTOL, \
                f"qgemv int8 err {err:.4f} vs fp32 oracle (want <= {INT8_RTOL})"
        else:
            assert err <= INT4_RTOL, err
    return rows


MX4_BYTES_RATIO = 0.28      # acceptance: mx4 stream vs the bf16 stream
FP8_BYTES_RATIO = 0.55      # acceptance: fp8 stream vs the bf16 stream


def bench_mx_qgemv(*, N, K, iters):
    """MX microscaling decode GEMV (DESIGN.md §11): fp4/fp8 values +
    E8M0 block exponents vs the bf16 stream, plus the fused swiglu and
    the grouped expert dispatch."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import repro.kernels as Kn
    from repro.kernels import ref as R
    from repro.quant import quantize_mx
    from repro.tune import REGISTRY
    from repro.tune.cache import get_tuned
    from repro.tune.search import roofline_time

    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    w = jax.random.normal(ks[0], (K, N), jnp.float32)   # stored (in, out)
    x = jax.random.normal(ks[1], (K,), jnp.float32)
    oracle = np.asarray(R.gemv(w.T, x))
    scale = float(np.max(np.abs(oracle)))

    wb, xb = w.T.astype(jnp.bfloat16), x.astype(jnp.bfloat16)
    bf_bytes = REGISTRY["gemv"].bytes(wb, xb)
    t_bf = _measure(lambda: Kn.gemv(wb, xb, get_tuned("gemv", wb, xb)),
                    iters)

    rows = []
    spec = REGISTRY["mx_qgemv"]
    for elem, gate in (("fp4", MX4_BYTES_RATIO), ("fp8", FP8_BYTES_RATIO)):
        qt = quantize_mx(w, elem=elem)
        args = (qt.values, qt.scales, x)
        q_bytes = spec.bytes(*args)
        qcfg = get_tuned("mx_qgemv", *args)
        t = _measure(lambda: Kn.mx_qgemv(*args, qcfg), iters)
        err = float(np.max(np.abs(np.asarray(Kn.mx_qgemv(*args, qcfg))
                                  - oracle)) / scale)
        ratio = q_bytes / bf_bytes
        tag = "mx4" if elem == "fp4" else "fp8"
        rows.append({
            "kernel": "mx_qgemv", "dtype": tag,
            "shape": f"N={N} K={K} block=32",
            "modeled_bytes": q_bytes, "bytes_ratio_vs_bf16": ratio,
            "measured_us": t * 1e6,
            "roofline_us": roofline_time(spec, args) * 1e6,
            "fraction_of_roofline": roofline_time(spec, args) / t,
            "max_rel_err_vs_fp32": err,
            "speedup_vs_bf16": t_bf / t,
        })
        assert ratio <= gate, \
            f"mx_qgemv {tag} modeled bytes {ratio:.3f}x bf16 (want <= {gate})"
        assert err <= (0.35 if elem == "fp4" else 0.10), \
            f"mx_qgemv {tag} err {err:.4f} vs fp32 oracle"

    # fused swiglu: two mx4 weight streams, one activation stream
    f = N
    kg, ku = jax.random.split(jax.random.PRNGKey(1))
    qg = quantize_mx(jax.random.normal(kg, (K, f), jnp.float32), elem="fp4")
    qu = quantize_mx(jax.random.normal(ku, (K, f), jnp.float32), elem="fp4")
    spec_s = REGISTRY["mx_qgemv_swiglu"]
    args_s = (qg.values, qg.scales, qu.values, qu.scales, x)
    t_s = _measure(lambda: Kn.mx_qgemv_swiglu(*args_s), iters)
    rows.append({
        "kernel": "mx_qgemv_swiglu", "dtype": "mx4",
        "shape": f"d={K} d_ff={f} block=32",
        "modeled_bytes": spec_s.bytes(*args_s),
        "bytes_ratio_vs_bf16": spec_s.bytes(*args_s) / (2 * bf_bytes),
        "measured_us": t_s * 1e6,
        "fraction_of_roofline": roofline_time(spec_s, args_s) / t_s,
    })

    # grouped expert dispatch: topk gathered stacks per router selection
    E, topk = 8, 2
    we = jax.random.normal(jax.random.PRNGKey(2), (E, K, N), jnp.float32)
    qe = quantize_mx(we, elem="fp4")
    xs = jnp.broadcast_to(x, (topk, K))
    ids = jnp.asarray([1, 5], jnp.int32)
    spec_g = REGISTRY["grouped_expert_qgemv"]
    args_g = (qe.values, qe.scales, xs, ids)
    t_g = _measure(lambda: Kn.grouped_expert_qgemv(*args_g), iters)
    got = np.asarray(Kn.grouped_expert_qgemv(*args_g))
    want = np.asarray(R.grouped_expert_qgemv(*args_g))
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
    rows.append({
        "kernel": "grouped_expert_qgemv", "dtype": "mx4",
        "shape": f"E={E} topk={topk} K={K} N={N}",
        "modeled_bytes": spec_g.bytes(*args_g),
        "bytes_ratio_vs_bf16": spec_g.bytes(*args_g) / (topk * bf_bytes),
        "measured_us": t_g * 1e6,
        "fraction_of_roofline": roofline_time(spec_g, args_g) / t_g,
    })
    return rows


def bench_engine_moe(*, slots, cache_len, requests, max_new):
    """Quantized-expert serving: bf16 vs int8 vs mx4 MoE engine tok/s,
    plus the modeled joules/token rows (the roofline move in energy)."""
    import numpy as np
    from repro.configs import get_config, reduced
    from repro.obs.energy import engine_energy_row
    from repro.serve import EngineConfig, build_engine
    from repro.serve.scheduler import Request

    cfg = reduced(get_config("qwen2-moe-a2.7b"))
    out = []
    for tag, qw in (("moe-bf16", "none"), ("moe-int8", "int8"),
                    ("moe-mx4", "mx4")):
        eng = build_engine(cfg, EngineConfig(
            slots=slots, cache_len=cache_len, backend="paged",
            quantize_weights=qw))
        rng = np.random.default_rng(0)
        for i in range(requests):
            eng.submit(Request(
                rid=i, prompt=rng.integers(1, min(cfg.vocab_size, 500),
                                           int(rng.integers(4, 12))),
                max_new_tokens=max_new))
        t0 = time.perf_counter()
        finished = eng.run_until_drained()
        m = eng.metrics()
        m.update({"engine": tag, "quantize_weights": qw,
                  "wall_s": time.perf_counter() - t0,
                  "all_finished": len(finished) == requests})
        assert m["all_finished"], f"{tag}: engine did not drain"
        out.append(m)

    energy = []
    for weights in ("bfloat16", "int8", "mx4", "fp8"):
        row = engine_energy_row(cfg, slots=slots, cache_len=cache_len,
                                weights=weights)
        row.pop("per_kernel", None)
        energy.append(row)
    j = {r["weights"]: r["joules_per_token"] for r in energy}
    assert j["mx4"] < j["int8"] < j["bfloat16"], \
        f"modeled joules/token must fall with the weight stream: {j}"

    # the acceptance invariant: a quantized-MoE decode step audits
    # byte-exact (measured kernel multiset == decode_step_account)
    from repro import obs
    from repro.models import RuntimeConfig, build_model
    audits = []
    for fmt in ("mx4", "fp8"):
        model = build_model(cfg, RuntimeConfig(remat="none",
                                               quantize_weights=fmt))
        a = obs.audit_decode_step(model, cache_len=cache_len)
        assert a.ok, a.report()
        audits.append({"arch": a.arch, "weights": fmt,
                       "kv_dtype": a.kv_dtype, "match": a.ok,
                       "dispatches": a.dispatches,
                       "modeled_bytes_measured": int(a.measured_bytes),
                       "modeled_bytes_expected": int(a.expected_bytes)})
    return out, energy, audits


def bench_paged_decode(*, B, S, page, iters):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import repro.kernels as Kn
    from repro.quant import quantize_kv
    from repro.tune import REGISTRY
    from repro.tune.cache import get_tuned
    from repro.tune.search import roofline_time

    KV, H, hd = 2, 4, 64
    nblk = -(-S // page)
    P = B * nblk + 1
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.bfloat16)
    k_pool = jax.random.normal(ks[1], (P, page, KV, hd), jnp.float32)
    v_pool = jax.random.normal(ks[2], (P, page, KV, hd), jnp.float32)
    perm = np.random.default_rng(0).permutation(P - 1) + 1
    bt = jnp.asarray(perm[:B * nblk].reshape(B, nblk), jnp.int32)
    length = jnp.full((B,), S - 1, jnp.int32)

    kb, vb = k_pool.astype(jnp.bfloat16), v_pool.astype(jnp.bfloat16)
    spec_bf = REGISTRY["paged_decode_attention"]
    args_bf = (q, kb, vb, bt, length)
    cfg = get_tuned(*(("paged_decode_attention",) + args_bf))
    t_bf = _measure(lambda: Kn.paged_decode_attention(*args_bf, cfg), iters)
    bf_bytes = spec_bf.bytes(*args_bf)
    oracle = np.asarray(
        Kn.paged_decode_attention(*args_bf, cfg), np.float32)
    scale = float(np.max(np.abs(oracle)))

    k8, ksc = quantize_kv(k_pool)
    v8, vsc = quantize_kv(v_pool)
    spec_q = REGISTRY["paged_decode_attention_int8"]
    args_q = (q, k8, ksc, v8, vsc, bt, length)
    qcfg = get_tuned(*(("paged_decode_attention_int8",) + args_q))
    t_q = _measure(
        lambda: Kn.paged_decode_attention_int8(*args_q, qcfg), iters)
    q_bytes = spec_q.bytes(*args_q)
    err = float(np.max(np.abs(np.asarray(
        Kn.paged_decode_attention_int8(*args_q, qcfg), np.float32)
        - oracle)) / scale)
    rows = [
        {"kernel": "paged_decode_attention", "dtype": "bfloat16",
         "shape": f"B={B} S={S} page={page} KV={KV} hd={hd}",
         "modeled_bytes": bf_bytes, "bytes_ratio_vs_bf16": 1.0,
         "measured_us": t_bf * 1e6,
         "fraction_of_roofline": roofline_time(spec_bf, args_bf) / t_bf},
        {"kernel": "paged_decode_attention_int8", "dtype": "int8",
         "shape": f"B={B} S={S} page={page} KV={KV} hd={hd}",
         "modeled_bytes": q_bytes,
         "bytes_ratio_vs_bf16": q_bytes / bf_bytes,
         "measured_us": t_q * 1e6,
         "fraction_of_roofline": roofline_time(spec_q, args_q) / t_q,
         "max_rel_err_vs_bf16": err,
         "speedup_vs_bf16": t_bf / t_q},
    ]
    assert q_bytes / bf_bytes <= 0.6, "int8 paged stream not under 0.6x"
    return rows


def bench_engine_int8(*, slots, cache_len, requests, max_new):
    """End-to-end: bf16-paged vs int8-paged engine tokens/s (greedy)."""
    import numpy as np
    from repro.configs import get_config, reduced
    from repro.serve import EngineConfig, build_engine
    from repro.serve.scheduler import Request

    cfg = reduced(get_config("qwen1.5-0.5b"))
    out = []
    for tag, kv in (("paged-bf16", ""), ("paged-int8", "int8")):
        eng = build_engine(cfg, EngineConfig(
            slots=slots, cache_len=cache_len, backend="paged",
            kv_cache_dtype=kv))
        rng = np.random.default_rng(0)
        for i in range(requests):
            eng.submit(Request(
                rid=i, prompt=rng.integers(1, min(cfg.vocab_size, 1000),
                                           int(rng.integers(4, 16))),
                max_new_tokens=max_new))
        t0 = time.perf_counter()
        finished = eng.run_until_drained()
        m = eng.metrics()
        m.update({"engine": tag, "wall_s": time.perf_counter() - t0,
                  "all_finished": len(finished) == requests})
        out.append(m)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="small shapes / fewer iterations (CI smoke)")
    ap.add_argument("--out", default="BENCH_quant.json")
    args = ap.parse_args(argv)

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    iters = 1 if args.fast else 3
    N, K = (256, 1024) if args.fast else (2048, 4096)
    S, page = (128, 32) if args.fast else (1024, 32)

    gemv_rows = bench_qgemv(N=N, K=K, iters=iters)
    mx_rows = bench_mx_qgemv(N=N, K=K, iters=iters)
    decode_rows = bench_paged_decode(B=4, S=S, page=page, iters=iters)
    engines = bench_engine_int8(slots=4, cache_len=64,
                                requests=4 if args.fast else 8,
                                max_new=4 if args.fast else 12)
    moe_engines, energy_rows, audit_rows = bench_engine_moe(
        slots=3, cache_len=64, requests=3 if args.fast else 6,
        max_new=4 if args.fast else 8)

    hdr = (f"{'kernel':<28}{'dtype':<10}{'bytes':>12}{'ratio':>8}"
           f"{'meas_us':>12}{'frac-roof':>12}{'rel-err':>10}")
    print(hdr)
    print("-" * len(hdr))
    for r in gemv_rows + mx_rows + decode_rows:
        err = r.get("max_rel_err_vs_fp32", r.get("max_rel_err_vs_bf16"))
        print(f"{r['kernel']:<28}{r['dtype']:<10}"
              f"{r['modeled_bytes']:>12.0f}"
              f"{r['bytes_ratio_vs_bf16']:>8.3f}"
              f"{r['measured_us']:>12.1f}"
              f"{r['fraction_of_roofline']:>12.3e}"
              + (f"{err:>10.4f}" if err is not None else ""))
    for m in engines:
        print(f"{m['engine']:<16} {m['decode_steps']:>4} steps  "
              f"{m['tokens_per_s']:>8.2f} tok/s  kv={m.get('kv_dtype')}")
    for m in moe_engines:
        print(f"{m['engine']:<16} {m['decode_steps']:>4} steps  "
              f"{m['tokens_per_s']:>8.2f} tok/s  "
              f"weights={m['quantize_weights']}")
    for r in energy_rows:
        print(f"energy/{r['weights']:<9} "
              f"{r['bytes_per_token']:>12,d} B/tok  "
              f"{r['joules_per_token'] * 1e3:>8.4f} mJ/tok")
    for a in audit_rows:
        print(f"audit/{a['weights']:<9} match={a['match']}  "
              f"{a['dispatches']} dispatches  "
              f"{a['modeled_bytes_measured']:,} B")

    from repro.core.troop import pallas_interpret
    payload = {
        "backend": jax.default_backend(),
        "interpret_mode": pallas_interpret(),
        "int8_rtol": INT8_RTOL, "int4_rtol": INT4_RTOL,
        "mx4_bytes_ratio": MX4_BYTES_RATIO,
        "fp8_bytes_ratio": FP8_BYTES_RATIO,
        "qgemv": gemv_rows,
        "mx": mx_rows,
        "paged_decode": decode_rows,
        "engines": engines,
        "moe_engines": moe_engines,
        "energy": energy_rows,
        "audit": audit_rows,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=1, default=str)
    print(f"wrote {args.out}")
    return payload


if __name__ == "__main__":
    main()
