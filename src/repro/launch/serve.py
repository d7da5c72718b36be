"""Serving launcher: ``python -m repro.launch.serve --arch <id> --smoke``.

Continuous-batching engine around the jitted prefill/decode steps (the
paper's decode workload): bucketed batched prefill (one compile per length
bucket), pluggable cache backend (``--backend paged`` is the default:
page-pool KV with block tables, see serve.kvcache).  ``--smoke`` uses the
reduced config on the host and prints the engine metrics.

``--tp N`` serves tensor-parallel over N devices (``repro.dist.tp``,
DESIGN.md §8).  Only under ``JAX_PLATFORMS=cpu`` does the launcher simulate
the mesh, by setting ``XLA_FLAGS=--xla_force_host_platform_device_count``
*before* JAX loads — which is why every heavyweight import lives inside
``main``.  Anywhere else ``--tp N`` needs N real devices.
"""
import argparse
import json
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel devices: shard heads/ffn/experts "
                         "+ KV pools under shard_map (1 = single device)")
    ap.add_argument("--tp-mode", choices=("exact", "overlap"),
                    default="exact",
                    help="exact: token-identical to tp=1; overlap: ring "
                         "collective matmuls (communication hidden behind "
                         "the GEMV, tolerance-equal)")
    ap.add_argument("--sync-dispatch", action="store_true",
                    help="disable the async submit/stream-out pipeline "
                         "(decode consumed in the cycle it was submitted)")
    ap.add_argument("--backend", choices=("dense", "paged"), default="paged")
    ap.add_argument("--kernel-decode", action="store_true",
                    help="attend via the tuned Pallas paged kernel (no "
                         "gathered dense view; interpreted, and slow, on "
                         "the CPU)")
    ap.add_argument("--chunked-prefill", action="store_true",
                    help="prefill as fixed-size token slabs interleaved "
                         "with decode (one compiled prefill shape, no "
                         "pow2 buckets; requires --backend paged, "
                         "attention-only archs)")
    ap.add_argument("--chunk-size", type=int, default=32,
                    help="tokens per prefill slab (--chunked-prefill)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share prompt-prefix KV pages across requests "
                         "(radix index + refcounts + copy-on-write; "
                         "requires --chunked-prefill)")
    ap.add_argument("--draft-arch", default=None, metavar="ID",
                    help="draft model for speculative decoding (a registry "
                         "arch id; reduced under --smoke like the target); "
                         "requires --speculate-k")
    ap.add_argument("--speculate-k", type=int, default=0,
                    help="speculative lookahead: draft K tokens per cycle "
                         "and verify K+1 positions in one target pass "
                         "(requires --draft-arch and --chunked-prefill; "
                         "greedy output is token-identical to K=0)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="tokens per page (default: the layout granule — "
                         "16 for bf16 pools, 32 for --kv-cache-dtype int8)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="pool pages per layer (default: full occupancy)")
    ap.add_argument("--quantize-weights",
                    choices=("none", "int8", "int4", "mx4", "fp8"),
                    default="none",
                    help="quantize matmul weights via repro.quant."
                         "quantize_params (MLP/attention projections; "
                         "embeddings/norms stay raw — DESIGN.md §5). "
                         "mx4/fp8 are the MX microscaling formats "
                         "(block-exponent E8M0 scales; MoE expert stacks "
                         "quantize too — DESIGN.md §11)")
    ap.add_argument("--quantize-group-size", type=int, default=128,
                    help="scale-group rows on the contraction axis (32-row "
                         "granule multiple; under --tp each weight shard "
                         "must hold whole groups — shrink for small archs)")
    ap.add_argument("--kv-cache-dtype", choices=("model", "int8"),
                    default="model",
                    help="int8: quantized KV (int8 page pools + scale "
                         "pages under --backend paged; per-slot int8 "
                         "caches under --backend dense)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record a lifecycle trace (repro.obs.Tracer) and "
                         "write it as a Chrome trace-event file — open in "
                         "ui.perfetto.dev (a .jsonl suffix writes "
                         "JSON-lines instead)")
    ap.add_argument("--profile", action="store_true",
                    help="install a repro.obs.DispatchProfiler on the "
                         "kernel-dispatch seam: per-phase dispatch counts, "
                         "modeled bytes and fraction-of-roofline (printed "
                         "after the run), kernel spans + streamed-bytes "
                         "counters on --trace-out, and the decode-step "
                         "dispatch audit (exits non-zero on mismatch)")
    ap.add_argument("--profile-out", default=None, metavar="PATH",
                    help="write the profiler summary (phases + per-kernel "
                         "rows + audit result) as JSON; implies --profile")
    args = ap.parse_args()

    if args.tp > 1 and os.environ.get("JAX_PLATFORMS") == "cpu" and \
            "--xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        # simulate the mesh on CPU: must land before jax is imported
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.tp}")

    import jax
    import numpy as np

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.configs import get_config, reduced
    from repro.models import RuntimeConfig, build_model
    from repro.models import modules as M
    from repro.serve import EngineConfig, build_engine, resolve_page_size
    from repro.serve.scheduler import Request

    if args.kernel_decode and args.backend != "paged":
        raise SystemExit("--kernel-decode requires --backend paged "
                         "(the kernel reads the page pool + block table)")
    if args.chunked_prefill and args.backend != "paged":
        raise SystemExit("--chunked-prefill requires --backend paged "
                         "(slabs write through block tables)")
    if args.prefix_cache and not args.chunked_prefill:
        raise SystemExit("--prefix-cache requires --chunked-prefill (a "
                         "prefix hit resumes prefill mid-prompt)")
    if args.draft_arch is not None and not args.speculate_k:
        raise SystemExit("--draft-arch requires --speculate-k > 0 (the "
                         "draft only runs when speculation is on)")
    if args.speculate_k:
        if args.draft_arch is None:
            raise SystemExit("--speculate-k requires --draft-arch (the "
                             "draft model that proposes the lookahead)")
        if not args.chunked_prefill:
            raise SystemExit("--speculate-k requires --chunked-prefill "
                             "(the verify pass reuses the chunked slab "
                             "attention path)")
        if args.tp > 1:
            raise SystemExit("--speculate-k is single-device for now "
                             "(drop --tp)")
    kv_int8 = args.kv_cache_dtype == "int8"
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
    if args.speculate_k and any(m != "attn" for (m, _) in cfg.layer_kinds()):
        raise SystemExit(f"--speculate-k supports causal-attention decoder "
                         f"archs only (the verify slab goes through the "
                         f"chunked attention path); {cfg.name} mixes in "
                         f"other mixer kinds")
    if args.quantize_weights in ("mx4", "fp8") and args.tp > 1:
        from repro.quant.tensor import granule
        if args.quantize_weights == "mx4":
            raise SystemExit(
                "--quantize-weights mx4 packs fp4 row pairs that would "
                "straddle the --tp shard boundary (mirrors the int4 "
                "packed-pair rejection in tp.plan); use fp8 under TP")
        if cfg.d_model % args.tp or (cfg.d_model // args.tp) % granule():
            raise SystemExit(
                f"--quantize-weights fp8 under --tp {args.tp}: the "
                f"{granule()}-row MX scale blocks must tile each weight "
                f"shard (d_model={cfg.d_model} does not hold a whole "
                f"number of blocks per shard)")
    engine_cfg = EngineConfig(
        slots=args.slots, cache_len=args.cache_len,
        backend=args.backend, page_size=args.page_size,
        num_pages=args.num_pages,
        kv_cache_dtype="int8" if kv_int8 else "",
        chunked_prefill=args.chunked_prefill, chunk_size=args.chunk_size,
        prefix_cache=args.prefix_cache, temperature=args.temperature,
        draft_arch=args.draft_arch, speculate_k=args.speculate_k,
        tp=args.tp, tp_mode=args.tp_mode,
        async_dispatch=not args.sync_dispatch,
        kernel_decode=args.kernel_decode,
        quantize_weights=args.quantize_weights,
        quantize_group_size=args.quantize_group_size).validate()
    args.page_size = resolve_page_size(engine_cfg)
    model = build_model(cfg, RuntimeConfig(
        remat="none", paged_kernel_decode=args.kernel_decode,
        quantize_weights=args.quantize_weights,
        kv_cache_dtype="int8" if kv_int8 else ""))
    params = M.unbox(model.init(jax.random.PRNGKey(0)))
    if args.quantize_weights in ("mx4", "fp8"):
        from repro.quant import quantize_params, quantized_stats
        try:
            params = quantize_params(params, fmt=args.quantize_weights,
                                     tp=args.tp)
        except AssertionError as e:
            raise SystemExit(str(e))
        qs = quantized_stats(params)
        print(f"quantized {qs['quantized_leaves']} weight leaves "
              f"({args.quantize_weights}): {qs['quantized_bytes']:,} B "
              f"(was {qs['quantized_fp32_bytes']:,} B fp32); "
              f"{qs['raw_bytes']:,} B left raw")
    elif args.quantize_weights != "none":
        from repro.quant import quantize_params, quantized_stats
        try:
            params = quantize_params(
                params, bits=8 if args.quantize_weights == "int8" else 4,
                group_size=args.quantize_group_size, tp=args.tp)
        except AssertionError as e:
            raise SystemExit(
                f"{e}\n(pass a smaller --quantize-group-size — it must "
                f"divide every projection's contraction extent"
                + (" per tp shard" if args.tp > 1 else "") + ")")
        qs = quantized_stats(params)
        print(f"quantized {qs['quantized_leaves']} weight leaves: "
              f"{qs['quantized_bytes']:,} B (was "
              f"{qs['quantized_fp32_bytes']:,} B fp32); "
              f"{qs['raw_bytes']:,} B left raw")
    draft = None
    if args.speculate_k and args.smoke:
        draft = reduced(get_config(args.draft_arch))

    extras = None
    if cfg.encoder_decoder or cfg.frontend == "vision":
        import jax.numpy as jnp
        F = cfg.cross_attention_len if cfg.encoder_decoder \
            else cfg.frontend_tokens
        extras = lambda req: {"frontend": 0.1 * jnp.ones(
            (1, F, cfg.d_model), jnp.bfloat16)}

    tracer = None
    if args.trace_out:
        from repro.obs import Tracer
        tracer = Tracer()
    profiler = None
    if args.profile or args.profile_out:
        from repro.obs import DispatchProfiler, decode_step_account
        profiler = DispatchProfiler(tracer=tracer)
        try:
            # seed the decode phase program from the modeled account (the
            # jnp decode path never hits the registry; the dispatch audit
            # below is what licenses this substitution)
            profiler.seed_phase("decode", decode_step_account(
                cfg, slots=args.slots, cache_len=args.cache_len,
                page_size=args.page_size,
                kv_dtype="int8" if kv_int8 else "bfloat16",
                weights=args.quantize_weights
                if args.quantize_weights in ("int8", "mx4", "fp8")
                else "bfloat16",
                quant_group=args.quantize_group_size))
        except ValueError as e:
            print(f"profile: decode account unavailable ({e}); decode "
                  f"phase reports occurrences/wall only")
        profiler.install()
    engine = build_engine(model, engine_cfg, params=params, draft=draft,
                          prefill_extras=extras, tracer=tracer,
                          profiler=profiler)
    rng = np.random.default_rng(0)
    system_prompt = rng.integers(1, min(cfg.vocab_size, 1000), 24) \
        if args.prefix_cache else None
    for i in range(args.requests):
        prompt = rng.integers(1, min(cfg.vocab_size, 1000),
                              int(rng.integers(4, 16)))
        if system_prompt is not None:       # shared header: exercise reuse
            prompt = np.concatenate([system_prompt, prompt])
        engine.submit(Request(rid=i, prompt=prompt,
                              max_new_tokens=args.max_new))
    finished = engine.run_until_drained()
    m = engine.metrics()
    print(f"served {len(finished)}/{args.requests} requests in "
          f"{engine.steps} decode steps "
          f"({m['prefill_traces']} prefill compiles, "
          f"backend={engine.backend.name})")
    print(json.dumps(m, indent=1, default=str))
    if tracer is not None:
        if args.trace_out.endswith(".jsonl"):
            tracer.to_jsonl(args.trace_out)
        else:
            tracer.to_chrome(args.trace_out)
        print(f"wrote {args.trace_out} ({len(tracer.events())} events, "
              f"{tracer.dropped} dropped)")
    if profiler is not None:
        profiler.uninstall()
        summary = profiler.summary()
        print(f"profile ({summary['spatz']}, roofline "
              f"{summary['roofline_bytes_per_s'] / 1e9:.0f} GB/s):")
        for row in summary["phases"]:
            print(f"  {row['phase']:>18s}: {row['occurrences']:5d} occ, "
                  f"{row['dispatches']:6d} dispatches, "
                  f"{row['modeled_bytes']:>14,d} B modeled, "
                  f"wall {row['wall_s'] * 1e3:8.1f} ms, "
                  f"roofline frac {row['fraction_of_roofline']:.2e}")
        audit_row = None
        if args.quantize_weights in ("none", "mx4", "fp8"):
            from repro.obs import audit_decode_step
            try:
                audit = audit_decode_step(model, cache_len=args.cache_len,
                                          page_size=args.page_size)
            except ValueError as e:
                print(f"dispatch audit skipped: {e}")
            else:
                print(audit.report())
                audit_row = {"ok": audit.ok, "arch": audit.arch,
                             "kv_dtype": audit.kv_dtype,
                             "dispatches": audit.dispatches,
                             "modeled_bytes": int(audit.measured_bytes)}
        else:
            print("dispatch audit skipped: quantized weights dequantize "
                  "in-graph (no qgemv dispatch to audit)")
        if args.profile_out:
            summary["audit"] = audit_row
            with open(args.profile_out, "w") as f:
                json.dump(summary, f, indent=1)
            print(f"wrote {args.profile_out}")
        if audit_row is not None and not audit_row["ok"]:
            raise SystemExit(1)


if __name__ == "__main__":
    main()
