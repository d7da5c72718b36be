"""Serving-engine benchmark -> table + BENCH_serve.json.

Runs the continuous-batching engine end to end in four modes — dense,
paged, chunked prefill, chunked + prefix cache (the last on a shared
system-prompt trace) — plus a speculative row (``speculative/k3``: a
same-arch seed-0 draft gives 100% greedy acceptance, so
``tokens_per_target_pass`` is deterministic, asserted > 1 and
exact-gated) on a reduced arch and reports decode steps/s,
tokens/s, per-request TTFT / decode rate, prefill-compile counts and
prefix-hit rates; then times the decode/prefill attention kernels (dense
and paged layouts) at the serving shapes and scores each as a measured
fraction-of-roofline (t_roofline / t_measured, tune subsystem
denominators).  Three extra chunked+prefix rows run the tensor-parallel
engine at tp=1/2/4 on a simulated 4-device host mesh — the modeled
per-device streamed-KV bytes are exact integers and gateable (a tp=4 row
must stream exactly 1/4 of the logical bytes per device).  One more
chunked+prefix row runs under a ``repro.obs.DispatchProfiler``
(mode ``chunked+prefix/profiled``): per-phase dispatch counts and modeled
bytes are deterministic and exact-gated.  ``--soak N`` adds an N-request
drain through the chunked+prefix engine (the nightly workload;
``--soak-tp 4`` adds a TP soak row; ``--soak-profile-trace PATH`` writes
the soak's Perfetto trace with per-kernel spans + streamed-bytes
counters); ``benchmarks/ci_gate.py`` gates the JSON against committed
baselines.

    PYTHONPATH=src python benchmarks/serve_bench.py --fast

Interpret-mode wall times on CPU are NOT TPU performance (see
DESIGN.md §3) — the value here is that the whole engine/kernel stack is
exercised for real and the numbers are comparable run over run.
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


MODES = ("dense", "paged", "chunked", "chunked+prefix")


def make_trace(cfg, rng, requests, max_new, *, shared_prefix=0):
    """Mixed-length prompt trace; ``shared_prefix`` > 0 prepends a common
    system prompt of that many tokens (the prefix-cache workload)."""
    import numpy as np
    from repro.serve.scheduler import Request
    head = rng.integers(1, min(cfg.vocab_size, 1000), shared_prefix) \
        if shared_prefix else None
    reqs = []
    for i in range(requests):
        prompt = rng.integers(1, min(cfg.vocab_size, 1000),
                              int(rng.integers(4, 20)))
        if head is not None:
            prompt = np.concatenate([head, prompt])
        reqs.append(Request(rid=i, prompt=prompt, max_new_tokens=max_new))
    return reqs


def bench_engine(arch: str, mode: str, *, slots, cache_len, requests,
                 max_new, page_size, chunk_size=16, tp=1, profiler=None,
                 speculate_k=0):
    import numpy as np
    from repro.configs import get_config, reduced
    from repro.serve import EngineConfig, build_engine

    cfg = reduced(get_config(arch))
    base = mode.split("/")[0]        # "chunked+prefix/tp4" -> "chunked+prefix"
    engine_cfg = EngineConfig(
        slots=slots, cache_len=cache_len,
        backend="dense" if base == "dense" else "paged",
        page_size=page_size,
        chunked_prefill=base.startswith("chunked") or speculate_k > 0,
        chunk_size=chunk_size, prefix_cache=(base == "chunked+prefix"),
        speculate_k=speculate_k, tp=tp)
    # same-arch draft with the factory's seed-0 params on both sides ->
    # 100% greedy acceptance: the speculative row is deterministic
    draft = reduced(get_config(arch)) if speculate_k else None
    eng = build_engine(cfg, engine_cfg, draft=draft, profiler=profiler)
    rng = np.random.default_rng(0)
    reqs = make_trace(cfg, rng, requests, max_new,
                      shared_prefix=24 if base == "chunked+prefix" else 0)
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    finished = eng.run_until_drained()
    wall = time.perf_counter() - t0
    m = eng.metrics()
    m.update({"arch": cfg.name, "mode": mode, "wall_s": wall,
              "requests_submitted": requests,
              "all_finished": len(finished) == requests})
    return m


def bench_profiled_engine(arch: str, *, slots, cache_len, requests,
                          max_new, page_size, chunk_size=16):
    """chunked+prefix engine run under a ``DispatchProfiler``: the engine
    row plus per-phase dispatch counts / modeled bytes (deterministic —
    exact CI gates) and wall-derived roofline fractions (info)."""
    from repro.configs import get_config, reduced
    from repro.obs import DispatchProfiler, decode_step_account

    cfg = reduced(get_config(arch))
    prof = DispatchProfiler()
    prof.seed_phase("decode", decode_step_account(
        cfg, slots=slots, cache_len=cache_len, page_size=page_size))
    prof.install()
    try:
        m = bench_engine(arch, "chunked+prefix/profiled", slots=slots,
                         cache_len=cache_len, requests=requests,
                         max_new=max_new, page_size=page_size,
                         chunk_size=chunk_size, profiler=prof)
    finally:
        prof.uninstall()
    m["profile"] = prof.phase_rows()
    return m


def bench_soak(arch: str, *, requests, slots, cache_len, page_size,
               chunk_size=16, tp=1, profile_trace=None, speculate_k=0):
    """N-request heavy-tail soak through the chunked+prefix engine under
    the deterministic step clock (``repro.obs``): percentile latency rows
    (engine cycles, gateable; wall seconds, info) plus queue-depth /
    occupancy timelines.  ``tp`` > 1 drains the same trace through the
    tensor-parallel engine (the nightly TP row).  ``profile_trace`` runs
    the soak under a ``DispatchProfiler`` feeding a ``Tracer`` and writes
    the Chrome trace (per-kernel spans + streamed-bytes counters) there."""
    from repro import obs
    _here = os.path.dirname(os.path.abspath(__file__))
    if _here not in sys.path:
        sys.path.insert(0, _here)
    from load_bench import build_engine

    tracer = prof = None
    if profile_trace:
        from repro.configs import get_config, reduced
        tracer = obs.Tracer()
        prof = obs.DispatchProfiler(tracer=tracer)
        prof.seed_phase("decode", obs.decode_step_account(
            reduced(get_config(arch)), slots=slots, cache_len=cache_len,
            page_size=page_size))
        prof.install()
    base = "speculative" if speculate_k else "chunked+prefix"
    cfg, eng = build_engine(arch, base, slots=slots,
                            cache_len=cache_len, page_size=page_size,
                            chunk_size=chunk_size, tracer=tracer,
                            profiler=prof, tp=tp, speculate_k=speculate_k)
    trace = obs.generate("heavy_tail", requests=requests, seed=0,
                         prompt_len=(4, min(48, cache_len - 18)),
                         max_new=(2, 16))
    try:
        rep = obs.Replayer(eng, timeline_every=4).run(
            trace, vocab_size=cfg.vocab_size)
    finally:
        if prof is not None:
            prof.uninstall()
    mode = f"soak/{base}" + (f"/k{speculate_k}" if speculate_k else "") \
        + (f"/tp{tp}" if tp > 1 else "")
    row = {"arch": cfg.name, "mode": mode,
           "dist": "heavy_tail", **rep.row()}
    if speculate_k:
        em = eng.metrics()
        row.update({k: em[k] for k in
                    ("speculate_k", "acceptance_rate",
                     "tokens_per_target_pass", "rollback_pages")})
    if profile_trace:
        tracer.to_chrome(profile_trace)
        print(f"wrote {profile_trace} ({len(tracer.events())} events, "
              f"{tracer.dropped} dropped)")
        row["profile"] = prof.phase_rows()
    tl = rep.timeline
    row["timeline"] = {k: [float(x) for x in tl[k]]
                       for k in ("t", "queue_depth", "decoding",
                                 "pages_in_use") if k in tl}
    return row


def bench_decode_kernels(*, slots, cache_len, page_size, iters):
    """Dense vs paged decode-attention at the serving shapes."""
    import jax
    import jax.numpy as jnp
    import repro.kernels  # noqa: F401  (populates the registry)
    from repro.tune import REGISTRY
    from repro.tune.cache import get_tuned
    from repro.tune.search import measure, roofline_time

    B, S, page = slots, cache_len, page_size
    KV, H, hd = 2, 4, 64
    nblk = -(-S // page)
    P = B * nblk + 1
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.bfloat16)
    length = jnp.full((B,), S - 1, jnp.int32)
    k_pool = jax.random.normal(ks[1], (P, page, KV, hd), jnp.bfloat16)
    v_pool = jax.random.normal(ks[2], (P, page, KV, hd), jnp.bfloat16)
    import numpy as np
    perm = np.random.default_rng(0).permutation(P - 1) + 1
    bt = jnp.asarray(perm[:B * nblk].reshape(B, nblk), jnp.int32)

    C = 16
    qc = jax.random.normal(ks[0], (B, C, H, hd), jnp.bfloat16)
    q_off = jnp.zeros((B,), jnp.int32)
    clen = jnp.full((B,), C, jnp.int32)
    cases = {
        "decode_attention": (q, k, v, length),
        "paged_decode_attention": (q, k_pool, v_pool, bt, length),
        "prefill_attention_paged": (qc, k_pool, v_pool, bt, q_off, clen),
    }
    rows = []
    for name, args in cases.items():
        spec = REGISTRY[name]
        cfg = get_tuned(name, *args)
        t = measure(spec, cfg, args, iters=iters)
        roof = roofline_time(spec, args)
        rows.append({
            "kernel": name,
            "shape": f"B={B} S={S} KV={KV} H={H} hd={hd}"
                     + (f" page={page}" if "paged" in name else ""),
            "measured_us": t * 1e6,
            "roofline_us": roof * 1e6,
            "fraction_of_roofline": roof / t if t else 0.0,
        })
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--fast", action="store_true",
                    help="fewer requests / timing iterations (CI smoke)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=64)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--max-new", type=int, default=None)
    ap.add_argument("--soak", type=int, default=0, metavar="N",
                    help="also run an N-request mixed-length drain through "
                         "the chunked+prefix engine (the nightly soak)")
    ap.add_argument("--soak-tp", type=int, default=0, metavar="TP",
                    help="with --soak: add one more soak row through the "
                         "tensor-parallel engine at this tp size")
    ap.add_argument("--soak-profile-trace", default=None, metavar="PATH",
                    help="with --soak: run the soak under a "
                         "DispatchProfiler and write a Chrome trace with "
                         "per-kernel spans + streamed-bytes counters "
                         "(open in ui.perfetto.dev)")
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args(argv)

    # Under JAX_PLATFORMS=cpu the tp rows simulate a 4-way mesh on the host;
    # the flag must land before the first jax import in this process.
    # Anywhere else the tp rows need four real devices.
    if os.environ.get("JAX_PLATFORMS") == "cpu" and \
            "--xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count=4")
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    requests = args.requests or (6 if args.fast else 12)
    max_new = args.max_new or (6 if args.fast else 16)
    iters = 1 if args.fast else 3

    engines = []
    for mode in MODES:
        m = bench_engine(args.arch, mode, slots=args.slots,
                         cache_len=args.cache_len, requests=requests,
                         max_new=max_new, page_size=args.page_size)
        engines.append(m)
        extra = (f"  prefix_hit {m['prefix_hit_rate']:.2f}"
                 if "prefix_hit_rate" in m else "")
        print(f"{mode:<15} {m['decode_steps']:>4} steps  "
              f"{m['decode_steps_per_s']:>8.2f} steps/s  "
              f"{m['tokens_per_s']:>8.2f} tok/s  "
              f"ttft {m['ttft_s_mean']*1e3:>7.1f} ms  "
              f"{m['prefill_traces']} prefill compiles{extra}")

    spec_k = 3
    m = bench_engine(args.arch, f"speculative/k{spec_k}", slots=args.slots,
                     cache_len=args.cache_len, requests=requests,
                     max_new=max_new, page_size=args.page_size,
                     speculate_k=spec_k)
    # the TROOP claim the row exists to gate: >1 emitted token per target
    # weight pass (1.0 would mean speculation bought nothing)
    assert m["tokens_per_target_pass"] > 1.0, (
        f"speculative engine emitted {m['tokens_per_target_pass']} tokens "
        f"per target pass (expected > 1 at same-arch 100% acceptance)")
    engines.append(m)
    print(f"{m['mode']:<15} {m['decode_steps']:>4} steps  "
          f"{m['tokens_per_s']:>8.2f} tok/s  "
          f"accept {m['acceptance_rate']:.2f}  "
          f"tok/pass {m['tokens_per_target_pass']:.2f}  "
          f"rollback {m['rollback_pages']} pages")

    for tp in (1, 2, 4):
        mode = f"chunked+prefix/tp{tp}"
        m = bench_engine(args.arch, mode, slots=args.slots,
                         cache_len=args.cache_len, requests=requests,
                         max_new=max_new, page_size=args.page_size, tp=tp)
        engines.append(m)
        print(f"{mode:<15} {m['decode_steps']:>4} steps  "
              f"{m['tokens_per_s']:>8.2f} tok/s  "
              f"ttft p95 {m['ttft_s_p95']*1e3:>7.1f} ms  "
              f"kv/dev {m['kv_bytes_streamed_per_device']:>9,} B  "
              f"overlap {m['dispatch_overlap_fraction']:.2f}")

    m = bench_profiled_engine(args.arch, slots=args.slots,
                              cache_len=args.cache_len, requests=requests,
                              max_new=max_new, page_size=args.page_size)
    engines.append(m)
    pdec = next((p for p in m["profile"] if p["phase"] == "decode"), {})
    print(f"{m['mode']:<15} {m['decode_steps']:>4} steps  "
          f"{m['tokens_per_s']:>8.2f} tok/s  "
          f"decode {pdec.get('dispatches', 0)} dispatches  "
          f"{pdec.get('modeled_bytes', 0):,} B modeled")

    soak = soak_tp = soak_spec = None
    if args.soak:
        soak = bench_soak(args.arch, requests=args.soak, slots=args.slots,
                          cache_len=args.cache_len,
                          page_size=args.page_size,
                          profile_trace=args.soak_profile_trace)
        print(f"soak({args.soak:>3})      "
              f"ttft_steps p50/p95/p99 {soak['ttft_steps_p50']:.1f}/"
              f"{soak['ttft_steps_p95']:.1f}/{soak['ttft_steps_p99']:.1f}  "
              f"queue max {soak['queue_depth_max']}  "
              f"drained={soak['all_finished']}")
        soak_spec = bench_soak(args.arch, requests=args.soak,
                               slots=args.slots, cache_len=args.cache_len,
                               page_size=args.page_size,
                               speculate_k=spec_k)
        print(f"soak/spec({args.soak:>3}) "
              f"ttft_steps p50/p95 {soak_spec['ttft_steps_p50']:.1f}/"
              f"{soak_spec['ttft_steps_p95']:.1f}  "
              f"accept {soak_spec['acceptance_rate']:.2f}  "
              f"tok/pass {soak_spec['tokens_per_target_pass']:.2f}  "
              f"drained={soak_spec['all_finished']}")
        if args.soak_tp > 1:
            soak_tp = bench_soak(args.arch, requests=args.soak,
                                 slots=args.slots, cache_len=args.cache_len,
                                 page_size=args.page_size, tp=args.soak_tp)
            print(f"soak/tp{args.soak_tp}({args.soak:>3})  "
                  f"ttft_steps p50/p95 {soak_tp['ttft_steps_p50']:.1f}/"
                  f"{soak_tp['ttft_steps_p95']:.1f}  "
                  f"overlap {soak_tp.get('dispatch_overlap_fraction', 0):.2f}"
                  f"  drained={soak_tp['all_finished']}")

    kernels = bench_decode_kernels(slots=args.slots, cache_len=args.cache_len,
                                   page_size=args.page_size, iters=iters)
    for r in kernels:
        print(f"{r['kernel']:<24} {r['measured_us']:>10.1f} us  "
              f"roof {r['roofline_us']:>8.3f} us  "
              f"frac {r['fraction_of_roofline']:.3e}")

    from repro.core.troop import pallas_interpret
    payload = {
        "backend": jax.default_backend(),
        "interpret_mode": pallas_interpret(),
        "engines": engines,
        "decode_kernels": kernels,
    }
    if soak is not None:
        payload["soak"] = soak
    if soak_tp is not None:
        payload["soak_tp"] = soak_tp
    if soak_spec is not None:
        payload["soak_spec"] = soak_spec
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=1, default=str)
    print(f"wrote {args.out}")
    return payload


if __name__ == "__main__":
    main()
